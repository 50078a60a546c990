"""A configuration of another CNN family joins the benchmark with new files
and new entries of ``BENCHMARK.json`` only.

In a copy of the benchmark, a family ``twin`` gets its reference and its
operation count (``bench/reference/twin.py``, ``bench/flops/twin.py``),
a configuration of that family, one cell with its traffic, limits, tiny
sizes and a per-layer metric, and their entries. The family's modules
re-export the CNN family's for ResNet under a model kind ``twin`` that the
CNN family does not know, so a harness that takes the CNN family's
reference whatever the configuration fails here. No file is overwritten.
There the harness's tests pass, the new cell among them: it resolves by
name and its reference's weights fit the program's model; and
``bench_faults``' sound run of it at its tiny sizes reads ``correct`` true."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
REFERENCE = '''"""Plain reference of the family ``twin``: the CNN family's
ResNet under a model kind of its own, which ``cnn`` does not know."""
from . import cnn
from .cnn import *  # noqa: F401,F403


def init_params(key, m):
    return cnn.init_params(key, dict(m, kind="resnet"))
'''
FLOPS = '''"""Operation count of the family ``twin``: the CNN family's, for
ResNet under a model kind of its own."""
from . import cnn
from .cnn import *  # noqa: F401,F403


def _as_resnet(f):
    return lambda m, *a, **kw: f(dict(m, kind="resnet"), *a, **kw)


(layers, forward_macs, train_flops_per_sample, conv_roofline_s,
 step_roofline_s) = map(_as_resnet, (
    cnn.layers, cnn.forward_macs, cnn.train_flops_per_sample,
    cnn.conv_roofline_s, cnn.step_roofline_s))
'''


def _add(root: Path, rel: str, text: str):
    path = root / rel
    assert not path.exists(), rel
    path.write_text(text)


def _copy_of_the_benchmark(to: Path):
    """``BENCHMARK.json`` and ``paths`` under ``to``, with the program
    beside them."""
    shutil.copy(ROOT / "BENCHMARK.json", to)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, to / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (to / "src").symlink_to(ROOT / "src")


def test_a_second_family_joins_with_new_files_only(tmp_path):
    _copy_of_the_benchmark(tmp_path)
    base = json.loads((ROOT / "bench/configs/resnet50.json").read_text())
    tiny = (ROOT / "bench/tests/tiny/resnet50.json").read_text()
    _add(tmp_path, "bench/reference/twin.py", REFERENCE)
    _add(tmp_path, "bench/flops/twin.py", FLOPS)
    _add(tmp_path, "bench/configs/twin50.json", json.dumps(dict(
        base, name="twin50", family="twin",
        model=dict(base["model"], kind="twin"))))
    _add(tmp_path, "bench/traffic/t128.json",
         (ROOT / "bench/traffic/b128.json").read_text())
    _add(tmp_path, "bench/limits/twin50.t128.json",
         (ROOT / "bench/limits/resnet50.b128.json").read_text())
    _add(tmp_path, "bench/tests/tiny/twin50.json", tiny)
    _add(tmp_path, "bench/metrics/twin_idle_pct.py",
         (ROOT / "bench/metrics/device_idle_pct.py").read_text())
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append(dict(SPEC["configs"][0], name="twin50",
                                file="bench/configs/twin50.json"))
    spec["workloads"].append({"name": "twin50.t128", "config": "twin50",
                              "traffic": "t128", "chips": 1,
                              "why": "a second family, one chip"})
    spec["per_layer"].append({
        "name": "twin_idle_pct", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "device",
        "moves": "samples_per_s", "workloads": ["twin50.t128"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(tmp_path), str(tmp_path / "src")]))
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-rA", "bench/tests/test_bench_harness.py"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    for test in ("test_every_cell_resolves_to_its_files[twin50.t128]",
                 "test_reference_weights_fit_the_program_model[twin50]"):
        assert f"PASSED bench/tests/test_bench_harness.py::{test}" \
            in out.stdout, out.stdout[-4000:]
    out = subprocess.run(
        [sys.executable, "-m", "bench.tests.bench_faults", "twin50.t128",
         "sound"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    sound = json.loads(out.stdout.splitlines()[-1])
    assert sound["fault"] == "sound" and sound["correct"] is True, sound
