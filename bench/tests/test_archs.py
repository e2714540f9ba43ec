"""What depends on the architecture is found by the configuration's
``model_type`` (``harness.arch``), and a type with no file is refused,
naming the file, before any device is touched."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import harness
import tiny


@pytest.mark.parametrize("model_type", ["no_such_arch", "../configs/x", ""])
def test_unknown_model_type_is_refused_with_the_path(model_type):
    c = tiny.cell("decode")
    c.config["config"]["model_type"] = model_type
    with pytest.raises(SystemExit) as e:
        harness.arch(c)
    assert str(harness.BENCH / "archs" / f"{model_type}.py") in str(e.value)


def test_granite_is_found_by_its_model_type():
    g = harness.arch(tiny.cell("decode"))
    assert g is harness.arch(tiny.cell("prefill"))
    assert Path(g.__file__) == harness.BENCH / "archs" / "granite.py"
    assert {"program_config", "forward_rows", "work"} <= set(vars(g))


def test_run_refuses_an_unknown_model_type_before_the_device(tmp_path):
    """bench/run.py exits on the missing file, not on the missing chip."""
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(harness.CHECKOUT / "BENCHMARK.json", tmp_path)
    (tmp_path / "src").symlink_to(harness.CHECKOUT / "src")
    conf = tmp_path / "bench" / "configs" / "granite-3-8b-l20.json"
    doc = json.loads(conf.read_text())
    doc["config"]["model_type"] = "no_such_arch"
    conf.write_text(json.dumps(doc))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "granite-3-8b-l20.decode", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    assert "archs/no_such_arch.py" in p.stderr
    assert "TPU" not in p.stderr
