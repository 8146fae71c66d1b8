"""What every cell shares: finding its files by name, the device check,
the compile cache, peaks, per-layer metric readers and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

    configs/<config>.json     sizes, source, cut, and what the program runs
    traffic/<traffic>.json    one traffic mix: its parameters, and the
                              driver module (``<driver>.py``) that runs it
    limits/<workload>.json    the limits ``correct`` is held to
    metrics/<metric>.py       ``read(run) -> float | None``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent


class Refused(Exception):
    """The run cannot be measured here (no chip, too few chips, no
    program): exit non-zero and print no result."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def checkout_root(bench_dir: Path = HERE) -> Path:
    """The directory that holds ``BENCHMARK.json``."""
    for d in [bench_dir, *bench_dir.parents]:
        if (d / "BENCHMARK.json").is_file():
            return d
    raise Refused(f"no BENCHMARK.json above {bench_dir}")


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict, cell: str, e2e_names: List[str]) -> bool:
    """A metric with ``workloads`` is the listed cells'; a per-layer one
    without it is every cell's that reports the metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names if "moves" in metric else True


def load_cell(name: str, root: Optional[Path] = None,
              bench_dir: Path = HERE) -> Cell:
    root = root or checkout_root(bench_dir)
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"unknown workload {name!r}; BENCHMARK.json has "
                      f"{sorted(cells)}")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = _json(root / conf["file"])
    traffic = _json(bench_dir / "traffic" / f"{w['traffic']}.json")
    lim_path = bench_dir / "limits" / f"{name}.json"
    limits = _json(lim_path) if lim_path.is_file() else {}
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, [])]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(name, int(w["chips"]), config, traffic, limits, e2e,
                per_layer)


# ---------------------------------------------------------------------------
# device, cache, program
# ---------------------------------------------------------------------------

def use_cache(root: Path) -> str:
    """The compile cache at ``<checkout>/.jax_cache``: set before JAX is
    imported, so the program's ``setup_compile_cache`` takes it."""
    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    return path


def import_program(root: Path):
    src = root / "src"
    if not (src / "repro").is_dir():
        raise Refused(f"no program under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def device_check(chips: int, platform: str = "tpu") -> Dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise Refused(f"needs a {platform}; JAX found {devs[0].platform!r} "
                      f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise Refused(f"needs {chips} chips; JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak(kind: str, key: str = "bf16_flops_per_s") -> float:
    table = _json(HERE / "peaks.json")["kinds"]
    if kind not in table:
        raise Refused(f"no peaks for device kind {kind!r} in peaks.json")
    return float(table[kind][key])


def memory_peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def model_config(config: Dict):
    """The program's config for this file: the registry entry, with the
    file's ``run_as`` sizes and ``program`` settings laid over it, so the
    run is what the file states."""
    from repro.configs import get_config

    return get_config(config["registry"]).with_(**config["run_as"],
                                                 **config.get("program", {}))


def check_layout(cfg, spec) -> None:
    """The benchmark's weights must have the program's tree and shapes."""
    import jax
    from repro.models.transformer import abstract_params

    prog, _ = abstract_params(cfg)
    ours = {tuple(path): tuple(shape) for path, shape, *_ in spec}
    theirs = {tuple(getattr(k, "key", k) for k in p): tuple(x.shape)
              for p, x in jax.tree_util.tree_flatten_with_path(prog)[0]}
    if ours != theirs:
        raise RuntimeError(f"weight layout differs from the program's: "
                           f"{sorted(set(ours.items()) ^ set(theirs.items()))}")


# ---------------------------------------------------------------------------
# per-layer metric readers
# ---------------------------------------------------------------------------

def reader(name: str, bench_dir: Path = HERE) -> Callable:
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(cell: Cell, run: Dict,
                   bench_dir: Path = HERE) -> Dict[str, Dict]:
    out = {}
    for m in cell.per_layer:
        value = reader(m["name"], bench_dir)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

def emit(result: Dict) -> None:
    """The compared numbers as the last lines of standard error, then the
    result as the last line of standard output, ``checks`` its last key."""
    checks = result.pop("checks", {})
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    result["checks"] = checks
    print(json.dumps(result), flush=True)


def judge(numbers: Dict[str, float], limits: Dict) -> Dict[str, Dict]:
    """Each number beside its limit; a number without a limit is an error
    in the cell's files."""
    out = {}
    for k, v in numbers.items():
        if k not in limits:
            raise RuntimeError(f"no limit for {k!r} in the cell's limits file")
        out[k] = {"value": float(v), "limit": float(limits[k])}
    return out


def all_within(checks: Dict[str, Dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
