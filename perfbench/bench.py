"""Shared pieces of the benchmark harness: where its files are, seeds,
the device check, the compile counter, the peak table and the result line.

Everything a cell needs is found by name: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``policies/<policy>.json``,
``runners/<runner>.py``, ``reference/<reference>.py`` and
``metrics/<metric>.py``. Adding a cell or a metric adds files; none of the
files here needs an edit.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
SRC = ROOT / "src"
CACHE_DIR = ROOT / ".jax_cache"
# where files are looked up by name, in order (tests add their own)
SEARCH = [HERE]


def _find(kind: str, name: str, ext: str) -> Path:
    for base in SEARCH:
        path = base / kind / f"{name}{ext}"
        if path.is_file():
            return path
    raise FileNotFoundError(f"no {kind}/{name}{ext} under {SEARCH}")


def load_json(kind: str, name: str) -> dict:
    with open(_find(kind, name, ".json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """Import ``perfbench/<kind>/<name>.py`` under a private module name
    (names may hold dots, which a plain import would read as packages)."""
    path = _find(kind, name, ".py")
    mod_name = f"perfbench_{kind}_{name.replace('.', '_').replace('-', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find_workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"workload {name!r} is not in BENCHMARK.json")


def load_cell(workload: str, seed: int, peaks_kind: Optional[str] = None,
              root: Path = ROOT):
    """The runner's cell for a workload, from the files its names point
    to."""
    wl = find_workload(benchmark_spec(root), workload)
    cfg = load_json("configs", wl["config"])
    traffic = load_json("traffic", wl["traffic"])
    policy = load_json("policies", traffic["policy"])
    runner = load_module("runners", traffic["runner"])
    peaks = peaks_for(peaks_kind or "TPU v5 lite")
    return runner.make(cfg, traffic, policy, seed, peaks)


def checks(readings: dict, limits: dict) -> List["Check"]:
    return [Check(k, readings[k], v) for k, v in limits.items()]


def metrics_for(spec: dict, workload: str, section: str) -> List[dict]:
    """The metrics of ``section`` (``end_to_end`` or ``per_layer``) that
    this cell reports. An end-to-end metric without a ``workloads`` list
    belongs to every cell; a per-layer one to every cell that reports the
    end-to-end metric it moves."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    if section == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


# ---------------------------------------------------------------- seeds
def seed_words(seed: int) -> List[int]:
    """A seed of any size as 32-bit words (for numpy's SeedSequence and
    JAX's key, neither of which takes more than 32 bits at once)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("--seed must be >= 0")
    words = [seed & 0xFFFFFFFF]
    seed >>= 32
    while seed:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
    return words


def np_rng(seed: int, *salt: int):
    import numpy as np
    return np.random.default_rng(seed_words(seed) + [int(s) for s in salt])


def jax_key(seed: int, salt: int = 0):
    import jax
    words = seed_words(seed)
    key = jax.random.PRNGKey(words[0])
    for w in words[1:] + [int(salt)]:
        key = jax.random.fold_in(key, w)
    return key


# ----------------------------------------------------------------- JAX
def configure_jax() -> None:
    """Persistent compilation cache at a fixed path inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def add_program_path() -> None:
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


class NoChip(RuntimeError):
    pass


def check_device(chips: int) -> dict:
    """The cell runs on TPUs only; anything else is an error, never a
    fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return device_info(chips)


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(chips, len(devs))}


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts programs lowered and compiled (or fetched from the
    persistent cache) through ``jax.monitoring``; the window must add
    none."""
    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0

        def listen(event, duration, **_):
            if event in self.EVENTS:
                self.count += 1
                self.seconds += duration
        jax.monitoring.register_event_duration_secs_listener(listen)


def peaks_for(kind: str) -> dict:
    """Peak FLOP/s and bytes/s of one chip, by ``device_kind``; an unknown
    device is an error."""
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


# ------------------------------------------------------------- results
@dataclass
class Check:
    """One number compared: ``ok`` when ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclass
class WindowResult:
    """What a runner's measured window did."""
    seconds: float                       # host time of the whole units
    end_to_end: Dict[str, float]         # metric name -> value
    attempted: int
    failed: int
    counters: Dict[str, float] = field(default_factory=dict)
    traced: Dict[str, Any] = field(default_factory=dict)


@dataclass
class Context:
    """What a per-layer metric's reader may look at."""
    workload: str
    config: dict
    traffic: dict
    policy: dict
    peaks: dict
    window: WindowResult
    trace: Any = None                    # reduce_trace.TraceSummary


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def kernel_groups() -> Dict[str, List[str]]:
    """Kernel names by group, from ``kernels.json``."""
    table = json.loads((HERE / "kernels.json").read_text())
    return {k: v for k, v in table.items() if k != "why"}
