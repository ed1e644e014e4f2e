"""Shared plumbing: the benchmark's files, seeds, quantiles, the compile
cache and the device."""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class BenchError(Exception):
    """A run that cannot produce a result (no chip, unknown device, a
    set-up check that failed)."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark_spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find(entries: Sequence[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(name: str, spec: Optional[dict] = None) -> tuple:
    """``(cell, config, traffic)`` for the workload called ``name``: the
    cell from ``spec`` (BENCHMARK.json by default), its configuration
    file and its traffic file ``bench/traffic/<traffic>.json``."""
    spec = spec or benchmark_spec()
    cell = find(spec["workloads"], name, "workload")
    conf = find(spec["configs"], cell["config"], "config")
    config = load_json(ROOT / conf["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{cell['traffic']}.json")
    return cell, config, traffic


def metrics_for(cell_name: str, trace: bool,
                spec: Optional[dict] = None) -> List[dict]:
    """The metrics a run of ``cell_name`` reports: the end-to-end ones
    with ``--trace 0``, the per-layer ones with ``--trace 1``."""
    spec = spec or benchmark_spec()
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in spec[kind]
            if cell_name in m.get("workloads", [cell_name])]


def rng(seed: int, tag: str) -> np.random.Generator:
    """A numpy generator for one purpose (``tag``) of one seed; any
    non-negative whole number is a valid seed."""
    return np.random.default_rng([int(seed), _tag_word(tag)])


def jax_key(seed: int, tag: str):
    """A JAX threefry key for one purpose of one seed, from 64 bits of
    ``numpy.random.SeedSequence`` so that seeds beyond 32 bits work."""
    import jax
    words = np.random.SeedSequence([int(seed), _tag_word(tag)]
                                   ).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def _tag_word(tag: str) -> int:
    return int.from_bytes(tag.encode()[:8].ljust(8, b"\0"), "little") \
        & 0xFFFFFFFF


def quantile(values: Sequence[float], q: float) -> float:
    """Nearest-rank quantile: the smallest value with at least ``q`` of
    the sample at or below it.  Exact for every sample, infinities
    included (a missing request counts as infinitely late)."""
    v = sorted(values)
    if not v:
        return float("nan")
    k = max(int(math.ceil(q * len(v))) - 1, 0)
    return float(v[k])


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX has read it already,
    otherwise ``<checkout>/.jax_cache``.  Every program is cached, so
    only the first run of a cell in a checkout compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir


def device_info(chips: int, allow_cpu: bool = False) -> dict:
    """The devices a cell runs on, with their peaks.  Raises
    :class:`BenchError` without a TPU, with fewer chips than the cell
    asks for, or on a device kind the peaks table lacks."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not allow_cpu:
        raise BenchError(f"no TPU: JAX found {dev.platform}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    peaks = load_json(BENCH_DIR / "peaks.json")["devices"]
    if dev.device_kind not in peaks and not allow_cpu:
        raise BenchError(f"device kind {dev.device_kind!r} is not in "
                         f"bench/peaks.json")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips, "devices": devices[:chips],
            "peaks": peaks.get(dev.device_kind)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (0 where the backend does
    not report it)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


@dataclass
class Check:
    """One number compared for ``correct``: it passes at or under its
    limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


@dataclass
class Run:
    """What one run measured, for the metric readers.  ``values`` holds
    the driver's named quantities (lists of per-request times, counter
    deltas, token counts); ``trace`` is a :class:`~bench.lib.trace.\
TraceView` of the traced window, or None."""
    cell: dict
    config: dict
    traffic: dict
    peaks: Optional[dict]
    seconds: float
    setup_s: float
    attempted: int
    failed: int
    memory_peak_bytes: int
    checks: List[Check]
    values: Dict[str, Any] = field(default_factory=dict)
    trace: Any = None

    @property
    def correct(self) -> bool:
        return all(c.ok for c in self.checks) and self.failed == 0
