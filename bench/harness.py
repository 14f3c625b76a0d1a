"""What every cell shares: finding its files by name, the device check,
the compile cache, seeds, and the result line.

A cell (`workloads` entry of ``BENCHMARK.json``) names a configuration
(``bench/configs/<file>``, whose ``family`` picks the module
``bench/families/<family>.py``), a traffic mix
(``bench/traffic/<traffic>.json``, whose ``kind`` picks the module
``bench/<kind>.py``) and its chip count.  Per-layer metrics are readers
``bench/metrics/<name>.py`` and correctness limits
``bench/limits/<workload>.json``; nothing here names a cell or an
architecture.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
# fixed path inside the checkout: the path is part of the cache key
CACHE_DIR = BENCH / ".cache" / "jax"
OUT_DIR = BENCH / ".cache" / "out"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> tuple:
    """(workload entry, config entry, traffic dict) for a cell name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, config, load_traffic(cell["traffic"])


def load_traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def load_config(entry: dict, root: Path = ROOT) -> dict:
    """A configuration file; one whose family has no module fails
    here."""
    conf = json.loads((root / entry["file"]).read_text())
    family(conf)
    return conf


def family(conf: dict):
    """The module ``bench/families/<family>.py`` of the family a
    configuration file names under ``"family"``, which has no default:
    what the benchmark knows of the architecture (its interface:
    `families.decoder`)."""
    name = conf["family"]
    try:
        return importlib.import_module(f"families.{name}")
    except ModuleNotFoundError as e:
        if e.name not in ("families", f"families.{name}"):
            raise
        raise ValueError(f"{conf['name']}: no module for family {name!r}; "
                         f"add bench/families/{name}.py") from None


def load_limits(workload: str) -> dict:
    return json.loads((BENCH / "limits" / f"{workload}.json").read_text())


def metrics_for(bench: dict, workload: str, trace: bool) -> list:
    """The metric entries a run of ``workload`` prints: its end-to-end
    metrics with ``trace`` off, its per-layer metrics with it on."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in bench[key]
            if workload in m.get("workloads", [workload])]


def metric_reader(name: str):
    """``read(run) -> float | None`` from ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(n: int, platform: str = "tpu"):
    """The devices a cell runs on; raises `NoChip` when JAX has no TPU or
    fewer than ``n`` of them.  Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"needs a {platform.upper()}, JAX found "
                     f"{devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at its fixed path in the
    checkout; every program is cached so the second run compiles
    nothing."""
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def add_program_path() -> None:
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


class _Built(Exception):
    pass


def launcher_model_kwargs(launcher, entry, *args) -> dict:
    """The keyword arguments with which the program's launcher module
    ``launcher`` builds its `Model`: ``entry(*args)`` runs the launcher
    up to that call, where a stand-in records them and stops it.  So a
    cell builds the model as the launcher does, whatever it sets."""
    seen = {}

    def stand_in(cfg, **kwargs):
        seen.update(kwargs)
        raise _Built

    saved = {name: getattr(launcher, name)
             for name in ("Model", "use_compile_cache")
             if hasattr(launcher, name)}
    launcher.Model = stand_in
    if "use_compile_cache" in saved:   # the cache is the benchmark's
        launcher.use_compile_cache = lambda: None
    try:
        entry(*args)
    except _Built:
        return seen
    finally:
        for name, value in saved.items():
            setattr(launcher, name, value)
    raise RuntimeError(f"{launcher.__name__} built no Model")


def seed_key(seed: int):
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    import jax
    key = jax.random.PRNGKey(0)
    seed = int(seed)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF,
                 seed >> 64):
        key = jax.random.fold_in(key, word & 0xFFFFFFFF)
    return key


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def load_peaks(kind: str) -> dict:
    """Peak rates of ``device_kind``; an unknown device is an error."""
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def print_result(*, correct: bool, attempted: int, failed: int,
                 metrics: dict, device: dict, checks: list,
                 breakdown: dict | None = None) -> None:
    """The result: ``checks`` (name, number, limit) as the last lines of
    standard error, and one JSON line last on standard output, with the
    same checks under the key that comes last."""
    for name, value, limit in checks:
        print(f"check {name} = {value!r} limit {limit!r}", file=sys.stderr)
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, value, limit in checks}
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def env_threads() -> None:
    """Few host threads: load comes from one process, steadily."""
    os.environ.setdefault("OMP_NUM_THREADS", "2")


class GcPauses:
    """Count and time the collector's pauses (a `gc.callbacks` entry)."""

    def __init__(self):
        self.n, self.seconds, self._t = 0, 0.0, None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.n += 1
            self.seconds += time.perf_counter() - self._t
            self._t = None

    def __str__(self):
        return f"gc pauses {self.n} taking {self.seconds!r} s"


def quiet_gc(pauses: GcPauses) -> None:
    """Before a window: collect the set-up's garbage and move what set-up
    left alive out of the collector's sight, so that no collection in the
    window walks it; time the collections that remain."""
    gc.collect()
    gc.freeze()
    gc.callbacks.append(pauses)


def loud_gc(pauses: GcPauses) -> None:
    """After a window: undo `quiet_gc`."""
    gc.callbacks.remove(pauses)
    gc.unfreeze()
