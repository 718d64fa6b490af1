"""What every cell shares: finding its files by name, the chip, the cache,
the per-layer readers and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  The
configuration's file is ``bench/configs/<config>.json`` (its reference is
``bench/reference/<family>.py``), the traffic mix is
``bench/traffic/<traffic>.json`` (its ``driver`` names
``bench/drivers/<driver>.py``), the limits of its correctness check are
``bench/limits/<cell>.json``, and each per-layer metric is read by
``bench/metrics/<metric>.py``.  A new cell, configuration or metric is new
files and entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = str(ROOT / ".jax_cache")


class HarnessError(RuntimeError):
    """The run cannot produce a result: no chip, a missing file, a
    configuration that disagrees with the program, a compile in the
    window.  The harness prints no result line."""


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise HarnessError(f"missing file {path.relative_to(ROOT)}") from None


def _for_cell(entries, cell: str):
    return [e for e in entries if cell in e.get("workloads", [cell])]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict              # bench/configs/<config>.json
    traffic: dict             # bench/traffic/<traffic>.json
    limits: dict              # bench/limits/<cell>.json
    end_to_end: list          # BENCHMARK.json metrics this cell reports
    per_layer: list

    @property
    def model(self) -> dict:
        return self.config["model"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise HarnessError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = _read_json(root / "bench" / "limits" / f"{name}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=_for_cell(spec["end_to_end"], name),
                per_layer=_for_cell(spec["per_layer"], name))


def require_program(root: Path = ROOT) -> None:
    """The system under test is the checkout's ``src/repro``."""
    if not (root / "src" / "repro" / "__init__.py").is_file():
        raise HarnessError("the program (src/repro) is not in this checkout")
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def require_chips(n: int) -> dict:
    """JAX's devices must be TPUs, at least ``n`` of them: no fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise HarnessError(f"no TPU: JAX's first device is "
                           f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < n:
        raise HarnessError(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n}


def enable_cache() -> str:
    """JAX's persistent compilation cache at the checkout's fixed path,
    through the program's own helper; every program, eager ones included,
    is cached, so only a checkout's first run compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    if path != CACHE_DIR or jax.config.jax_compilation_cache_dir != path:
        raise HarnessError(f"compile cache at {path!r}, not {CACHE_DIR!r}: "
                           f"checkout_env() must run before JAX is imported")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peaks(device_kind: str) -> dict:
    table = _read_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise HarnessError(f"device {device_kind!r} is not in bench/peaks.json")
    return table[device_kind]


def model_sizes_agree(config: dict) -> None:
    """The configuration file's sizes are the registry's, key by key."""
    from repro.configs import get_arch
    cfg = get_arch(config["arch"])
    have = {**dataclasses.asdict(cfg), "head_dim": cfg.resolved_head_dim}
    bad = {k: (v, have.get(k)) for k, v in config["model"].items()
           if have.get(k) != v}
    if bad:
        raise HarnessError(f"{config['arch']}: file and registry differ "
                           f"(file, registry): {bad}")


def reference(config: dict):
    return importlib.import_module(f"bench.reference.{config['family']}")


def driver(traffic: dict):
    return importlib.import_module(f"bench.drivers.{traffic['driver']}")


def _load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(cell: Cell, record: dict) -> dict:
    """Each per-layer metric of the cell from its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = _load_reader(m["name"])(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def emit(*, correct: bool, attempted: int, failed: int, metrics: dict,
         device: dict, checks: dict, breakdown: Optional[dict]) -> None:
    """The last stderr lines: each compared number beside its limit.  The
    last stdout line: the result, with the checks last."""
    for name, c in checks.items():
        say(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    sys.stdout.flush()
    print(json.dumps(line), flush=True)


def checkout_env() -> None:
    """Settings that keep the run's files inside its checkout; before JAX
    is imported, which reads the cache directory from the environment."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
