"""Phase tracing: named scopes in-program, span timers on the host.

Three instruments, matched to where time can hide in the pipeline:

* :meth:`PhaseTracer.scope` — ``jax.named_scope`` annotations on every
  pipeline phase (pack/send, signal release, acquire/wait, unpack,
  force tiers, integrate, rolling prune, rebin seam) so XLA profiles and
  HLO dumps carry the paper's phase vocabulary.  Scopes are pure
  metadata: they are applied *unconditionally* and cannot perturb the
  schedule — trajectories stay bitwise-identical with tracing on.

* :meth:`PhaseTracer.step_metrics` — on-device per-step event counters
  derived from the :class:`~repro.core.pipeline.ledger.SignalLedger`
  state threaded through the scan.  Enabled tracers add ``obs/*`` int32
  outputs to the step metrics dict; they are *extra outputs* computed
  from counters the carry already holds, never extra sequencing — the
  barrier structure (and therefore the trajectory) is untouched.

* :func:`span` / :func:`time_fn` — the host-side timing API every
  hand-rolled ``perf_counter`` loop in ``benchmarks/`` and
  ``launch/dryrun.py`` now shares.  ``span`` is a context manager whose
  ``sync()`` method pins async-dispatched device values so the clock
  stops only after the work is done (the ``md_worker`` bug class RA008
  lints against); ``time_fn`` is the warmup+iters median loop.  A span
  is also a ``jax.profiler.TraceAnnotation`` named ``obs.<name>``, so
  under a profiler session the host spans sit on the device trace's
  clock beside the ``obs.<phase>`` scopes of the programs they launch.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

# the phase vocabulary (paper Fig. 6 lanes); scopes are free-form but
# these names are what the trace reduction reads and the README
# documents.
PHASES = (
    "pack_send",          # gather halo payload + issue puts (fwd)
    "fwd_release",        # coordinate put-with-signal released
    "fwd_acquire",        # consumer's signal wait before reading halo
    "force",              # extended-block pair forces (tier ladder)
    "rev_release",        # force-return put released at fill time
    "rev_return",         # the force-return exchange itself
    "rev_acquire",        # integrator's wait on returned forces
    "integrate_begin",    # kick-drift half step
    "integrate_finish",   # final kick
    "roll_prune",         # rolling inner prune between rebins
    "rebin_seam",         # rebin/migration gather at the block seam
    "rebin",              # rebin/migration program body
    "rebin_force",        # its velocity-Verlet force carry (dense pass)
    "prune",              # pair-schedule prune program body
)

# the host spans of MDEngine's block loop (``obs.<name>`` on the
# profiler trace; ``simulate`` is the parent of the rest)
HOST_SPANS = (
    "simulate",           # one MDEngine.simulate call
    "rebin_dispatch",     # rebin_fn call
    "prune_dispatch",     # prune_fn call
    "schedule_read",      # prune histogram reads + tier bucketing
    "block_dispatch",     # block program call
    "overflow_read",      # rolling-prune overflow scalar read
    "diag_read",          # migration diagnostics read
    "metrics_read",       # per-step metrics read + concatenate
    "snapshot",           # step_counters record + registry snapshot
)


@dataclass(frozen=True)
class PhaseTracer:
    """Per-engine tracing switch, threaded into :class:`StepPipeline`.

    ``scope`` is always active (metadata-only).  ``step_metrics`` is the
    part that grows the program's output signature, so it is gated on
    ``enabled`` — the default :data:`NULL_TRACER` adds nothing and the
    compiled program is byte-for-byte the pre-obs one.
    """

    enabled: bool = False

    def scope(self, name: str):
        """Named scope ``obs.<name>`` for one pipeline phase.

        A scope is debug metadata, which JAX's persistent compilation
        cache leaves out of its key: an executable loaded from the cache
        carries the scopes of the compile that stored it.  A scope added
        to a program whose key is unchanged reaches a profile only once
        that entry is gone.
        """
        return jax.named_scope(f"obs.{name}")

    def step_metrics(self, ledger, led) -> Dict[str, jnp.ndarray]:
        """Per-step ledger counters as extra ``obs/*`` metric outputs."""
        if not self.enabled:
            return {}
        return {
            "obs/in_flight": jnp.asarray(ledger.in_flight(led), jnp.int32),
            "obs/released": jnp.asarray(led.released.sum(), jnp.int32),
            "obs/acquired": jnp.asarray(led.acquired.sum(), jnp.int32),
            "obs/clobbers": jnp.asarray(led.clobbers.sum(), jnp.int32),
        }


NULL_TRACER = PhaseTracer(enabled=False)


def is_obs_metric(key: str) -> bool:
    """True for metric keys owned by tracing (``obs/`` prefix)."""
    return key.startswith("obs/")


def strip_obs_metrics(metrics: Dict[str, Any]) -> Dict[str, Any]:
    """The physics-only view of a step-metrics dict."""
    return {k: v for k, v in metrics.items() if not is_obs_metric(k)}


# --------------------------------------------------------------------------
# host-side spans
# --------------------------------------------------------------------------

class Span:
    """One timed host-side region; ``dur`` is valid after the ``with``."""

    __slots__ = ("name", "meta", "t0", "dur", "_sync")

    def __init__(self, name: str, meta: dict):
        self.name = name
        self.meta = meta
        self.t0 = 0.0
        self.dur = 0.0
        self._sync: Any = None

    def sync(self, tree):
        """Register device values to ``block_until_ready`` before the
        clock stops (returns ``tree`` so call sites stay one-liners)."""
        self._sync = (tree,) if self._sync is None else self._sync + (tree,)
        return tree


@contextlib.contextmanager
def span(name: str, registry=None, **meta):
    """Time a host-side region on ``perf_counter``.

    Any value passed through ``sp.sync(...)`` is blocked on before the
    stop-read, so async-dispatched device work is inside the measurement.
    With a registry, emits a ``span`` record and observes the duration in
    the ``span/<name>`` histogram.  The region is also the profiler
    annotation ``obs.<name>`` (one TraceMe check with no session open).
    """
    sp = Span(name, meta)
    with jax.profiler.TraceAnnotation(f"obs.{name}"):
        sp.t0 = time.perf_counter()
        try:
            yield sp
        finally:
            if sp._sync is not None:
                jax.block_until_ready(sp._sync)
            sp.dur = time.perf_counter() - sp.t0
            if registry is not None:
                registry.emit("span", name=name, t0=sp.t0, dur=sp.dur,
                              **meta)
                registry.histogram(f"span/{name}").observe(sp.dur)


@dataclass
class TimingResult:
    """Per-iteration wall times from :func:`time_fn` (seconds)."""

    name: str
    times: List[float]

    @property
    def median(self) -> float:
        vs = sorted(self.times)
        return vs[len(vs) // 2]

    @property
    def best(self) -> float:
        return min(self.times)

    @property
    def mean(self) -> float:
        return sum(self.times) / len(self.times)


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 10,
            name: Optional[str] = None, registry=None) -> TimingResult:
    """Median-of-``iters`` timing with compile warmup and a hard
    ``block_until_ready`` inside every measured iteration."""
    label = name or getattr(fn, "__name__", "fn")
    for _ in range(max(0, warmup)):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    result = TimingResult(name=label, times=times)
    if registry is not None:
        registry.emit("timing", name=label, iters=len(times),
                      median_s=result.median, best_s=result.best)
        registry.histogram(f"timing/{label}").observe(result.median)
    return result
