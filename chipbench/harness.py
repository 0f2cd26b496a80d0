"""What every driver shares: finding a cell's files by name, the chip
check, the compile cache, host spans, the trace window and the result
line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Its files
are found by name, so a new cell, configuration, traffic mix or
per-layer metric is a new file and never an edit:

* ``configs/<config>.json`` — the configuration as it is run, with its
  plain reference ``configs/<config>.py`` beside it;
* ``traffic/<traffic>.json`` — the mix's parameters, and under
  ``driver`` the general generator in ``drivers/<driver>.py`` that
  reads them;
* ``work/<config>.py`` — the operations the algorithm needs, from shapes;
* ``limits/<workload>.json`` — the limit of each number ``correct`` is
  decided on;
* ``metrics/<metric>.py`` — one reader per per-layer metric;
* ``peaks.json`` — the chip's published peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / ".out"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def import_file(path: Path, name: Optional[str] = None):
    """Import one benchmark file as a module (file names may hold dots)."""
    name = name or "chipbench_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload with everything found for it by name."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def driver(self) -> str:
        return self.traffic["driver"]

    def reference(self):
        return import_file(BENCH / "configs" / f"{self.config_name}.py")

    def work(self):
        return import_file(BENCH / "work" / f"{self.config_name}.py")


def _for_cell(metrics: List[dict], name: str) -> List[dict]:
    return [m for m in metrics if name in m.get("workloads", [name])]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=load_json(BENCH / "configs" / f"{w['config']}.json"),
                traffic_name=w["traffic"],
                traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                limits=load_json(BENCH / "limits" / f"{name}.json"),
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name))


# ---- the device -------------------------------------------------------------

class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def check_chip(n_chips: int, platform: str = "tpu"):
    """The devices the cell runs on; anything but ``n_chips`` chips of
    ``platform`` is refused (a test passes its own platform)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"JAX's devices are {devs[0].platform!r}, not "
                     f"{platform!r}: the benchmark measures the chip only")
    if len(devs) < n_chips:
        raise NoChip(f"the cell asks for {n_chips} chips, JAX has "
                     f"{len(devs)}")
    return devs[:n_chips]


def device_info(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "memory_peak_bytes": memory_peak(devs)}


def memory_peak(devs) -> int:
    """Peak bytes on the fullest chip.  The TPU runtime keeps a
    program's temporaries in its reserved region, so the larger of the
    in-use and reserved peaks is the one that counts."""
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        peaks.append(max(int(st.get("peak_bytes_in_use", 0)),
                         int(st.get("peak_bytes_reserved", 0))))
    return max(peaks)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding
    every program however quickly it compiled."""
    import jax
    path = os.environ.get(CACHE_ENV) or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


# ---- host spans -------------------------------------------------------------

class Spans:
    """Host-clock spans around the calls into each layer, named as the
    profiler's ``TraceAnnotation`` is, so the trace shows them too."""

    def __init__(self):
        self.times: Dict[str, List[float]] = defaultdict(list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.times[name].append(time.perf_counter() - t0)

    def clear(self):
        self.times.clear()


@contextlib.contextmanager
def traced(workload: str):
    """Capture a profiler trace into a fixed directory of the checkout;
    yields the directory (emptied first)."""
    import jax
    d = OUT / "trace" / workload
    shutil.rmtree(d, ignore_errors=True)
    d.mkdir(parents=True)
    jax.profiler.start_trace(str(d))
    try:
        yield d
    finally:
        jax.profiler.stop_trace()


def trace_reducer():
    """``trace.py``, loaded under a name of its own (the standard
    library has a ``trace`` module too)."""
    return import_file(BENCH / "trace.py", "chipbench_trace")


# ---- per-layer metrics --------------------------------------------------------

def read_per_layer(cell: Cell, ctx: dict) -> Dict[str, dict]:
    """Run each per-layer metric's reader on ``ctx``; a reader that
    finds nothing returns None and its metric is left out."""
    out = {}
    for m in cell.per_layer:
        reader = import_file(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---- the result ---------------------------------------------------------------

@dataclasses.dataclass
class Outcome:
    """What a driver hands back to ``run.py``."""

    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    compared: Dict[str, float]
    per_layer_ctx: Optional[dict] = None
    device: Optional[dict] = None
    breakdown: Optional[dict] = None


def verdict(compared: Dict[str, float], limits: Dict[str, float]):
    """``correct`` and the lines that show each number beside its limit."""
    checks = {}
    ok = bool(limits)
    for name, limit in limits.items():
        value = compared.get(name)
        finite = value is not None and math.isfinite(value)
        ok = ok and finite and value <= limit
        # a number that is not finite prints as null: JSON has no inf
        checks[name] = {"value": value if finite else None,
                        "limit": limit}
    return ok, checks


def result_line(cell: Cell, out: Outcome, trace: bool) -> dict:
    ok, checks = verdict(out.compared, cell.limits["limits"])
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    if trace:
        metrics = read_per_layer(cell, out.per_layer_ctx or {})
    else:
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in out.end_to_end.items() if k in units}
    line = {"correct": ok, "attempted": int(out.attempted),
            "failed": int(out.failed), "metrics": metrics,
            "device": out.device}
    if trace and out.breakdown:
        line["breakdown"] = out.breakdown
    line["checks"] = checks
    return line


def report_checks(line: dict, compared: Dict[str, float],
                  stream=sys.stderr):
    """Numbers read without a limit first, then each compared number
    beside its limit, as the last lines."""
    for name, value in compared.items():
        if name not in line["checks"]:
            print(f"reading {name} {value!r} (no limit)", file=stream,
                  flush=True)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=stream, flush=True)
