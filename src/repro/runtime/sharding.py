"""Scope schedule -> GSPMD sharding rules (the paper's ISP/WSP on a TPU mesh).

Storage rule (paper SSIII-B, distributed weight buffering): parameters are
ALWAYS stored sharded over the ``model`` axis on their heavy dimension.
* ISP-zone layers compute directly on the shards (Megatron-style tensor
  parallelism) -- activations stay replicated over ``model``.
* WSP-zone layers keep activations *sequence-sharded* over ``model``; GSPMD
  then all-gathers the (sharded-stored) weights at use -- which is exactly
  the paper's "chiplets exchange weight tiles in the preparation phase".

The WSP->ISP transition point from the Scope DSE maps to ``transition_repeat``
on the scanned layer stack; zone 1 runs under the WSP constraints, zone 2
under ISP (models/model.py executes the two scan segments).

Table II correspondence (verified in tests/test_runtime_sharding.py by
counting HLO collectives):
* WSP->WSP boundary: halo only        -> no collective on the residual
  (attention K/V gathers play the halo role),
* WSP->ISP transition: all-gather of the sequence-sharded activations,
* ISP->ISP: all-reduce after row-parallel matmuls.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig
from ..optim import OptState

WSP, ISP = "WSP", "ISP"


@dataclass(frozen=True)
class ShardPlan:
    """Execution plan for one (arch x shape x mesh) cell."""
    mesh_axes: tuple[str, ...]            # ("pod","data","model") | ("data","model")
    p1: str = ISP                         # zone-1 partition
    p2: str = ISP                         # zone-2 partition
    transition_repeat: int | None = None  # None -> single zone (p1)
    ep: bool = True                       # expert parallelism for MoE weights
    zero: bool = True                     # optimizer state sharded over data too
    use_dp: bool = True                   # False when batch < dp size (long_500k)
    # Pipeline stages of the Scope schedule behind this plan, as
    # (layer_lo, layer_hi, chip_type, region_chips) tuples.  On mixed-flavor
    # packages consecutive stages may carry different chip types; the
    # serving executor maps each stage onto its flavor's sub-mesh.  Empty
    # for plans not derived from a cluster-level schedule.
    stage_chip_types: tuple[tuple[int, int, str | None, int], ...] = ()
    meta: dict = field(default_factory=dict, hash=False, compare=False)

    @property
    def dp(self):
        """Batch data-parallel axes."""
        if not self.use_dp:
            return ()
        return tuple(a for a in self.mesh_axes if a in ("pod", "data"))

    def zone_partition(self, zone: int) -> str:
        return self.p1 if zone == 1 else self.p2


# ------------------------------------------------------------- param specs

def _attn_specs(cfg: ModelConfig, model_div_kv: bool) -> dict:
    kv_spec = P(None, None, "model") if model_div_kv else P(None, None, None)
    return {
        "wq": P(None, None, "model"),
        "wk": kv_spec,
        "wv": kv_spec,
        "wo": P(None, "model", None),
    }


def _ffn_specs() -> dict:
    return {"w1": P(None, None, "model"), "w2": P(None, "model", None),
            "w3": P(None, None, "model")}


def _moe_specs(ep: bool) -> dict:
    if ep:
        # experts over 'model' + FSDP-style 'data' shard on the hidden dim:
        # a 772 GB expert bank over 16 chips alone is 48 GB/chip (> HBM);
        # GSPMD all-gathers the tile at use (paper SSIII-B semantics).
        e = P(None, "model", "data", None)
        return {"router": P(None, None, None), "w1": e, "w2": e, "w3": e}
    return {
        "router": P(None, None, None),
        "w1": P(None, None, None, "model"),
        "w2": P(None, None, "model", None),
        "w3": P(None, None, None, "model"),
    }


def _mamba_specs() -> dict:
    return {
        "in_proj": P(None, None, "model"),
        "conv_w": P(None, None, "model"),
        "conv_b": P(None, "model"),
        "x_proj": P(None, "model", None),
        "dt_proj": P(None, None, "model"),
        "dt_bias": P(None, "model"),
        "A_log": P(None, "model", None),
        "D": P(None, "model"),
        "out_proj": P(None, "model", None),
    }


def _rwkv_specs() -> dict:
    return {
        "mu": P(None, None, None),
        "wr": P(None, None, "model"),
        "wk": P(None, None, "model"),
        "wv": P(None, None, "model"),
        "wg": P(None, None, "model"),
        "wo": P(None, "model", None),
        "w0": P(None, None),
        "w_lora_a": P(None, None, None),
        "w_lora_b": P(None, None, "model"),
        "u": P(None, "model", None),
        "ln_x": P(None, None),
        "cm_r": P(None, None, "model"),
        "cm_k": P(None, None, "model"),
        "cm_v": P(None, "model", None),
    }


def param_pspecs(cfg: ModelConfig, plan: ShardPlan, mesh: Mesh) -> dict:
    """PartitionSpec pytree matching init_params' structure."""
    model_size = mesh.shape["model"]
    model_div_kv = cfg.n_kv_heads % model_size == 0 or model_size % cfg.n_kv_heads == 0
    blocks = []
    for pi, kind in enumerate(cfg.expanded_pattern):
        spec = {"ln1": P(None, None), "ln2": P(None, None)}
        if kind in ("attn", "local"):
            spec["attn"] = _attn_specs(cfg, model_div_kv)
        elif kind == "mamba":
            spec["mamba"] = _mamba_specs()
        elif kind == "rwkv":
            spec["rwkv"] = _rwkv_specs()
        if kind == "rwkv":
            pass
        elif cfg.is_moe_block(pi):
            spec["moe"] = _moe_specs(plan.ep)
            if not cfg.ffn_gated:
                spec["moe"].pop("w3")
        else:
            spec["ffn"] = _ffn_specs()
            if not cfg.ffn_gated:
                spec["ffn"].pop("w3")
        blocks.append(spec)
    out = {
        "embed": P("model", None),          # vocab-sharded
        "blocks": tuple(blocks),
        "final_ln": P(None),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = P(None, "model")
    return out


def opt_pspecs(cfg: ModelConfig, plan: ShardPlan, mesh: Mesh, param_specs, optimizer: str):
    """Optimizer-state specs.  ZeRO mode adds a 'data' shard on the repeat
    axis of stacked block params (paper SSIII-B applied to optimizer state)."""
    def zero_ify(spec: P) -> P:
        if not plan.zero or len(spec) == 0:
            return spec
        if spec[0] is None and "data" in plan.mesh_axes:
            return P("data", *spec[1:])
        return spec

    def map_spec(s):
        return zero_ify(s) if isinstance(s, P) else s

    moment_specs = jax.tree.map(map_spec, param_specs,
                                is_leaf=lambda x: isinstance(x, P))
    if optimizer == "adamw":
        return OptState(step=P(), m=moment_specs, v=moment_specs)
    # adafactor: row stats drop the last dim, col stats drop the 2nd-to-last
    def rows(s):
        if not isinstance(s, P):
            return s
        return zero_ify(P(*s[:-1])) if len(s) >= 2 else s

    def cols(s):
        if not isinstance(s, P):
            return s
        if len(s) >= 2:
            return zero_ify(P(*s[:-2], s[-1]))
        return P(None)

    return OptState(
        step=P(),
        m=jax.tree.map(rows, param_specs, is_leaf=lambda x: isinstance(x, P)),
        v=jax.tree.map(cols, param_specs, is_leaf=lambda x: isinstance(x, P)),
    )


# -------------------------------------------------------------- activations

def make_constrain(mesh: Mesh, plan: ShardPlan, zone: int):
    """Activation-constraint callback for models.forward/decode_step."""
    dp = plan.dp
    partition = plan.zone_partition(zone)

    def constrain(x, tag: str):
        if tag == "moe:groups":
            # token groups [G, Tg, d]: G shards over every mesh axis
            spec = P(tuple([*dp, "model"]), *([None] * (x.ndim - 1)))
        elif tag == "moe:buffers":
            # expert buffers [E, G*Cg, d]: shard experts (EP) or capacity
            # rows -- NEVER replicate (the biggest MoE activation tensor).
            if plan.ep:
                spec = P("model", tuple(dp), *([None] * (x.ndim - 2)))
            else:
                spec = P(None, tuple([*dp, "model"]), *([None] * (x.ndim - 2)))
        elif tag == "logits":
            spec = P(dp, None, "model")
        elif partition == WSP and x.ndim >= 3 and x.shape[1] > 1:
            spec = P(dp, "model", *([None] * (x.ndim - 2)))
        else:
            spec = P(dp, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return constrain


# ------------------------------------------------------------------- caches

# KV heads a device must hold for a head split: eight fill the sublane tile
# of a bf16 ``[.., KV, hd]`` layer, T(8,128)(2,1), which attention reads at
# full rate; with fewer the tile is padded, XLA copies each layer's K and V
# out of the stack, and the dots read the padded tiles slowly.
MIN_HEADS_A_DEVICE = 8


def kv_split(n_kv_heads: int, model_size: int,
             slots: int | None = None) -> str | None:
    """The axis of the K/V caches ``[R, B, T, KV, hd]`` that the ``model``
    axis (``model_size`` devices) splits, from shapes alone.

    ``"heads"`` where each device then holds at least
    ``MIN_HEADS_A_DEVICE`` KV heads; else ``"slots"`` where ``model_size``
    divides the ``slots`` a data shard holds (each device then holds whole
    slots, every head, all positions); else ``"heads"`` where they divide
    the axis at all; else ``"positions"``.  None on one device."""
    if model_size == 1:
        return None
    heads = n_kv_heads % model_size == 0
    if heads and n_kv_heads // model_size >= MIN_HEADS_A_DEVICE:
        return "heads"
    if slots is not None and slots % model_size == 0:
        return "slots"
    return "heads" if heads else "positions"


def cache_pspecs(cfg: ModelConfig, plan: ShardPlan, split: str | None) -> tuple:
    """Specs of the stacked decode caches ``[R, B, T, ...]``.

    K/V ``[R, B, T, KV, hd]`` split over the ``model`` axis on the axis
    ``split`` (``kv_split``) names.  Over heads each device holds the heads
    its shard of ``wk``/``wv`` computes, so a row is written where it is
    made; over slots attention runs where its slots are (``over_slots``);
    over positions GSPMD combines the shards' partial softmax.  Mamba and
    rwkv states split over their channels."""
    dp = plan.dp
    kv = {None: P(None, dp, None, "model", None),
          "heads": P(None, dp, None, "model", None),
          "slots": P(None, (*dp, "model"), None, None, None),
          "positions": P(None, dp, "model", None, None)}[split]
    specs = []
    for kind in cfg.expanded_pattern:
        if kind in ("attn", "local"):
            specs.append({"k": kv, "v": kv})
        elif kind == "mamba":
            specs.append({
                "h": P(None, dp, "model", None),
                "conv": P(None, dp, None, "model"),
            })
        elif kind == "rwkv":
            specs.append({
                "S": P(None, dp, "model", None, None),
                "shift": P(None, dp, None, None),
                "shift_ffn": P(None, dp, None, None),
            })
    return tuple(specs)


def over_slots(mesh: Mesh, plan: ShardPlan):
    """``attend`` hook of ``models.decode_step`` where K/V split over slots.

    The q/k/v projections arrive column-split over ``model`` (each device
    holds its heads for every slot).  One all-to-all moves them, as one
    array, from heads to slots; the device then writes its slots' new rows,
    and runs scores, softmax and P·V on its own slots with every head and
    its local cache; one all-to-all takes the output back to heads, where
    the row-split ``wo`` and its all-reduce expect it."""
    m = mesh.shape["model"]
    slots = (*plan.dp, "model")
    cols = P(plan.dp, None, "model")

    def attend(core, q, k, v, position, cache_k, cache_v, layer):
        widths = [a.shape[-1] // m for a in (q, k, v)]
        stack = P(*[None] * (cache_k.ndim - 4), slots, None, None, None)

        def local(q, k, v, position, cache_k, cache_v, layer):
            qkv = jnp.concatenate([q, k, v], -1)            # [b, 1, c / m]
            qkv = jax.lax.all_to_all(qkv, "model", 0, 1, tiled=True)
            q, k, v = (a.reshape(a.shape[0], 1, -1) for a in jnp.split(
                qkv, [widths[0], widths[0] + widths[1]], -1))
            out, cache_k, cache_v = core(q, k, v, position, cache_k, cache_v,
                                         layer)
            out = out.reshape(out.shape[0], m, -1)          # [b / m, m, .]
            out = jax.lax.all_to_all(out, "model", 1, 0, tiled=True)
            return out, cache_k, cache_v

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(cols, cols, cols, P(slots), stack, stack, P()),
            out_specs=(cols, stack, stack),
        )(q, k, v, position, cache_k, cache_v, layer)

    return attend


def batch_pspecs(cfg: ModelConfig, plan: ShardPlan, with_labels: bool = True):
    dp = plan.dp
    tok = P(dp, None)
    spec = {}
    if cfg.frontend != "audio_stub":      # audio stub has no token input
        spec["tokens"] = tok
    if with_labels:
        spec["labels"] = tok
    if cfg.frontend != "none":
        spec["frontend_embeds"] = P(dp, None, None)
    return spec


def to_shardings(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _axes_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        n = 1
        for a in entry:
            n *= mesh.shape[a]
        return n
    return mesh.shape[entry]


def zero_shard(spec_tree, shape_tree, mesh: Mesh, axis: str = "data"):
    """Shape-aware ZeRO placement: for each optimizer-moment leaf, put the
    ``data`` axis on the first unsharded dim whose size it divides (the
    naive dim-0 choice dies on the divisibility sanitizer for most layer
    counts -- 40, 42, 52 repeats vs a 16-way axis)."""
    if axis not in mesh.shape:
        return spec_tree
    n = mesh.shape[axis]

    def fix(spec, shaped):
        if not isinstance(spec, P):
            return spec
        entries = list(spec) + [None] * (len(shaped.shape) - len(spec))
        if axis in entries:
            return spec
        for i, (e, dim) in enumerate(zip(entries, shaped.shape)):
            if e is None and dim % n == 0 and dim >= n:
                entries[i] = axis
                return P(*entries)
        return spec

    return jax.tree.map(fix, spec_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, P))


def sanitize_pspecs(spec_tree, shape_tree, mesh: Mesh):
    """Drop shard axes whose size does not divide the array dim.

    jit in_shardings/out_shardings require exact divisibility (unlike
    with_sharding_constraint); non-divisible cases (40 rwkv heads over a
    16-way model axis, 21 gemma2 repeats over a 16-way ZeRO axis, ...) fall
    back to replication on that dim.
    """
    def fix(spec, shaped):
        if not isinstance(spec, P):
            return spec
        shape = shaped.shape
        out = []
        for i, entry in enumerate(spec):
            if i >= len(shape) or shape[i] % _axes_size(mesh, entry) != 0:
                out.append(None)
            else:
                out.append(entry)
        return P(*out)

    return jax.tree.map(fix, spec_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, P))
