"""What every cell shares: the refusal without a chip, the compile
meter, device memory, seeds, the per-layer metric readers and the
result line.

Nothing here knows a model or a traffic mix: `run.py` finds a cell's
configuration, traffic and driver by the names in `BENCHMARK.json`, and
each per-layer metric by its own file under `bench/metrics/`.
"""
from __future__ import annotations

import importlib.util
import json
import math
import pathlib
import sys

import jax

from bench.lib import counts

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
# a traced run traces at most this much of its window: a trace of a
# whole window is too large to read back within a run's time
TRACE_SECONDS = 8.0


class Refused(SystemExit):
    """The run cannot measure what the cell asks for; exits non-zero
    and prints no result."""

    def __init__(self, msg):
        super().__init__(f"bench: {msg}")


def require_chips(n_chips: int) -> tuple:
    """(devices, peak row).  Refuses a host without a TPU, with fewer
    chips than the cell needs, in a kernel mode other than pallas, or
    of a device kind missing from the peak table."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: jax sees platform {devs[0].platform!r}")
    if len(devs) < n_chips:
        raise Refused(f"the cell needs {n_chips} chips, jax sees "
                      f"{len(devs)}")
    from repro.kernels import ops
    mode = ops.kernel_mode()
    if mode != "pallas":
        raise Refused(f"kernel mode is {mode!r}, not 'pallas'")
    try:
        peak = counts.peaks(devs[0].device_kind)
    except KeyError as e:
        raise Refused(str(e)) from None
    return devs[:n_chips], peak


def enable_compile_cache() -> str:
    """JAX's persistent cache through the program's own helper
    (`$JAX_COMPILATION_CACHE_DIR` when set, else `<checkout>/.jax_cache`),
    with every program cached, however short its compile, so that a warm
    run compiles nothing."""
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileMeter:
    """Backend compiles and persistent-cache hits/misses, from
    jax.monitoring (a cache load counts as a compile event too)."""

    def __init__(self):
        self.compiles, self.secs, self.hits, self.misses = 0, 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> tuple:
        return self.compiles, self.secs, self.hits, self.misses

    def since(self, mark) -> dict:
        c, s, h, m = mark
        return {"compiles": self.compiles - c,
                "compile_s": self.secs - s,
                "cache_hits": self.hits - h,
                "cache_misses": self.misses - m}


def memory(devices) -> dict:
    """Per device: bytes in use now and the process's high-water mark."""
    out = {}
    for d in devices:
        st = d.memory_stats() or {}
        out[d.id] = {"bytes_in_use": st.get("bytes_in_use"),
                     "peak_bytes_in_use": st.get("peak_bytes_in_use")}
    return out


def seed_key(seed: int):
    """A PRNG key from any whole number: the low 32 bits make the key,
    the rest is folded in, so seeds above 2**32 stay distinct."""
    if seed < 0:
        raise ValueError("--seed must be >= 0")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


def load_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def load_module(path, name: str):
    """Import a benchmark file by path (names may hold dots)."""
    mod_name = "bench_" + name.replace(".", "_").replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_per_layer(metrics: list, workload: str, ctx: dict) -> dict:
    """Run each per-layer metric's reader (`bench/metrics/<name>.py`,
    `read(ctx) -> number | None`) that lists this cell, and keep what
    they found.  A reader that finds nothing returns None and its metric
    is left out of the line."""
    out = {}
    for m in metrics:
        if workload not in m.get("workloads", [workload]):
            continue
        reader = load_module(BENCH_DIR / "metrics" / f"{m['name']}.py",
                             m["name"])
        value = reader.read(ctx)
        if value is None:
            continue
        if not math.isfinite(value):
            raise ValueError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def trace_options():
    """Profiler options of a traced run: device and host events, no
    Python function tracing."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def checks_of(read: dict, limits: dict) -> dict:
    """Each compared number beside its limit; a number that is not
    finite fails.  A number whose limit is null is read and printed but
    not compared (a cell where it separates nothing)."""
    return {k: {"value": v, "limit": limits[k],
                "ok": limits[k] is None
                or bool(math.isfinite(v) and v <= limits[k])}
            for k, v in read.items()}


def emit(result: dict, checks: dict):
    """Print each compared number beside its limit as the last lines on
    standard error, then the result as the last line on standard output,
    with the checks under the key that comes last."""
    for name, c in checks.items():
        verdict = ("not compared" if c["limit"] is None
                   else "ok" if c["ok"] else "FAILED")
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"({verdict})", file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    print(json.dumps(line), flush=True)


def note(tag: str, **fields):
    """A diagnostic line before the result (never the last line)."""
    print(f"[{tag}] " + json.dumps(fields, default=str), flush=True)
