"""One run of one cell: set-up, the measured window, the check against the
plain reference, and the result line.

A system module (``systems/<name>.py``) provides

- ``setup(ctx) -> state``: the deployment built on the device from the seed
  and warmed up on every shape the window uses;
- ``window(ctx, state, seconds, span) -> record``: the cell's closed loop
  for ``seconds``; ``span(name)`` opens a host span the trace keeps;
- ``check(ctx, state, record) -> dict``: ``{name: (value, limit)}``, the
  numbers compared with the plain reference, each correct while
  ``value <= limit``; it runs after the window and may free the program;
- ``end_to_end(ctx, record) -> dict``: the cell's end-to-end metrics;
- ``layer_inputs(ctx, record) -> dict``: what the per-layer readers read
  besides the trace (counts and least times);
- ``attempted_failed(record, checks) -> (attempted, failed)``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
import time
import jax

from harness import peaks as peaks_mod, spec as spec_mod, trace as trace_mod


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Ctx:
    cell: spec_mod.Cell
    seed: int
    devices: list
    peaks: dict

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def key(self, stream: int):
        """A PRNG key of stream ``stream`` of this run's seed (any whole
        number; seeds beyond 32 bits fold their high half in)."""
        s = int(self.seed) % (1 << 64)
        k = jax.random.PRNGKey(s & 0xFFFFFFFF)
        k = jax.random.fold_in(k, s >> 32)
        return jax.random.fold_in(k, stream)


def tpu_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; NoChip where there are fewer."""
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no accelerator: {e}") from None
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``<checkout>/.jax_cache``, a fixed path. Every
    program is cached, however quick its compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@contextlib.contextmanager
def _span(name: str):
    with jax.profiler.TraceAnnotation(name):
        yield


def peak_memory(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(ctx: Ctx, seconds: float, trace: bool, t_start: float) -> dict:
    """Set-up, window, check; returns the result line's object."""
    system = spec_mod.load_system(ctx.config["system"])
    state = system.setup(ctx)
    setup_s = time.perf_counter() - t_start
    _log(f"setup_s {setup_s}")
    summary = None
    if trace:
        cap = None
        try:
            with trace_mod.capture() as cap:
                record = system.window(ctx, state, seconds, _span)
            summary = trace_mod.reduce(trace_mod.load_xplane(cap["path"]))
        finally:
            if cap is not None:
                trace_mod.discard(cap)
    else:
        record = system.window(ctx, state, seconds, _span)
    memory = peak_memory(ctx.devices)
    checks = system.check(ctx, state, record)
    del state
    correct = all(v <= lim for v, lim in checks.values())
    attempted, failed = system.attempted_failed(record, checks)
    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices), "memory_peak_bytes": memory}
    if summary is None:
        metrics = {m: {"value": float(v), "unit": _unit(ctx.cell, m)}
                   for m, v in system.end_to_end(ctx, record).items()}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    else:
        run = {"trace": summary, "config": ctx.config,
               "traffic": ctx.traffic, "peaks": ctx.peaks,
               **system.layer_inputs(ctx, record)}
        metrics = spec_mod.read_per_layer(ctx.cell, run)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = trace_mod.breakdown(summary)
    result["checks"] = {n: {"value": float(v), "limit": float(lim)}
                        for n, (v, lim) in checks.items()}
    for n, (v, lim) in checks.items():
        _log(f"check {n} {v} limit {lim} {'ok' if v <= lim else 'FAILED'}")
    return result


def _unit(cell: spec_mod.Cell, metric: str) -> str:
    for m in cell.end_to_end:
        if m["name"] == metric:
            return m["unit"]
    raise KeyError(f"{metric!r} is not an end-to-end metric of {cell.name}")


def make_ctx(cell: spec_mod.Cell, seed: int, devices: list) -> Ctx:
    return Ctx(cell=cell, seed=seed, devices=devices,
               peaks=peaks_mod.for_kind(devices[0].device_kind))
