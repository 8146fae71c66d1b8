"""The harness: found by name, seeded, and it refuses to measure off the
chip."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

import gen
import harness
import run
from conftest import BENCH, ROOT


def _bench_copy(tmp_path):
    """The checkout's BENCHMARK.json and benchmark directory, copied."""
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return bench


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    """A new configuration, traffic mix with its own driver, limits file
    and per-layer metric take only new files and new entries in
    BENCHMARK.json, and a run of the new cell goes through them."""
    bench = _bench_copy(tmp_path)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    conf = json.loads((bench / "configs" / "nanochat-d20.json").read_text())
    (bench / "configs" / "new-model.json").write_text(
        json.dumps(dict(conf, name="new-model")))
    (bench / "traffic" / "new-mix.json").write_text(
        json.dumps({"driver": "new_driver", "rate": 0.25}))
    (bench / "new_driver.py").write_text(
        "def run(cell, seed, seconds, trace_dir, t_start):\n"
        "    return {'new_e2e_s': cell.traffic['rate'] * 4, 'setup_s': 1.0,\n"
        "            'memory_peak_bytes': 7, 'attempted': 3, 'failed': 0,\n"
        "            'numbers': {'gap': 0.1}}\n")
    (bench / "limits" / "new-cell.json").write_text('{"gap": 0.5}')
    (bench / "metrics" / "new_metric.x.py").write_text(
        "def read(run):\n    return run['x'] * 2\n")
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "new-model", "source": "https://example.org",
                         "file": "benchmarks/chip/configs/new-model.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "new-cell", "config": "new-model",
                           "traffic": "new-mix", "chips": 1, "why": "test"})
    b["end_to_end"].append({"name": "new_e2e_s", "unit": "s",
                            "better": "lower", "bound": 0.05,
                            "source": "host_clock", "workloads": ["new-cell"]})
    b["per_layer"].append({"name": "new_metric.x", "unit": "%",
                           "better": "lower", "source": "program_counter",
                           "layer": "test", "moves": "new_e2e_s",
                           "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    cell = harness.load_cell("new-cell", tmp_path, bench)
    assert cell.config["name"] == "new-model"
    assert cell.traffic["rate"] == 0.25
    assert cell.limits == {"gap": 0.5}
    assert {m["name"] for m in cell.end_to_end} == {"new_e2e_s", "setup_s"}
    assert [m["name"] for m in cell.per_layer] == ["new_metric.x"]
    got = harness.read_per_layer(cell, {"x": 21.0}, bench)
    assert got == {"new_metric.x": {"value": 42.0, "unit": "%"}}
    monkeypatch.syspath_prepend(str(bench))
    out = run.measure(cell, 5, 1.0, False, tmp_path,
                      {"platform": "cpu", "kind": "cpu", "count": 1},
                      time.perf_counter())
    assert out["correct"] and out["attempted"] == 3
    assert out["metrics"]["new_e2e_s"] == {"value": 1.0, "unit": "s"}
    assert out["checks"] == {"gap": {"value": 0.1, "limit": 0.5}}
    # nothing that was there changed
    assert all(p.read_bytes() == v for p, v in before.items())


def test_every_cell_resolves():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in b["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.limits, w["name"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.reader(m["name"]))


def test_train_rows_deterministic_per_seed():
    a = gen.train_batch(2**33 + 7, 5, 1, 1, 64, 512)
    b = gen.train_batch(2**33 + 7, 5, 1, 1, 64, 512)
    c = gen.train_batch(2**33 + 8, 5, 1, 1, 64, 512)
    d = gen.train_batch(2**33 + 7, 6, 1, 1, 64, 512)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert not np.array_equal(a["tokens"], d["tokens"])
    np.testing.assert_array_equal(a["tokens"][..., 1:], a["labels"][..., :-1])
    assert a["tokens"].shape == (1, 1, 64)


def test_seeds_fit_numpy_and_jax():
    big = 2**40 + 1
    assert 0 <= gen.jax_seed(big) < 2**31
    assert gen.jax_seed(big) == gen.jax_seed(big)
    assert gen.jax_seed(big, 0) != gen.jax_seed(big, 1)
    assert gen.jax_seed(big) != gen.jax_seed(big + 1)


def test_settle_waits_for_dispatched_work():
    """The window's edges wait for the work already sent to the device."""
    import jax
    import jax.numpy as jnp

    import train

    f = jax.jit(lambda a: jnp.tanh(a @ a) @ a)
    outs = [f(jnp.full((512, 512), i / 512.0)) for i in range(4)]
    train.settle()
    assert all(o.is_ready() for o in outs)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "d20-train-diloco", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_the_cpu():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "needs a tpu" in p.stderr


def test_refuses_without_the_program(tmp_path):
    _bench_copy(tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
