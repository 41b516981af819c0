"""Seeded open-loop arrival processes in VIRTUAL time.

The benchmark's own copy of ``bigdl_tpu/serving/arrivals.py`` (PR 24): a
later PR may change the program's, not the yardstick.  The contract: the offered sequence — arrival times and how many there are
— is exactly (seed, shape, rate, duration)-determined, because the
phase/diurnal multiplier and termination read *virtual* time only and
each yielded arrival consumes exactly ONE ``rng.exponential`` draw.
Wall clock only paces the replay, so two runs with the same seed offer
bit-identical traces regardless of host speed.

Shapes:

  * ``TRACES`` — the step-function phase shapes (``steady`` / ``burst``
    / ``overload``) as ``(start_fraction, rate_multiplier)`` tuples,
    applied via :func:`mult_at`;
  * :func:`diurnal_mult` — one smooth day-cycle over the run: a raised
    cosine from ``trough`` at the run's edges to ``peak`` mid-run, the
    slow rate swell an autoscaler must track (step bursts test
    *reaction*, the diurnal swell tests *anticipation*).

``serve_bench.py --arrivals diurnal`` composes it with any ``--trace``
phases (multipliers multiply).
"""
from __future__ import annotations

import math
from typing import Callable, Iterator, Optional, Sequence, Tuple

#: --trace shapes as (start_fraction_of_run, rate_multiplier) phases
TRACES = {
    "steady": ((0.0, 1.0),),
    "burst": ((0.0, 1.0), (0.4, 6.0), (0.6, 1.0)),
    "overload": ((0.0, 1.0), (0.3, 4.0)),
}

Phases = Sequence[Tuple[float, float]]


def mult_at(phases: Phases, frac: float) -> float:
    """The step-function rate multiplier at ``frac`` of the run."""
    m = phases[0][1]
    for start, mult in phases:
        if frac >= start:
            m = mult
    return m


def diurnal_mult(frac: float, peak: float = 3.0,
                 trough: float = 0.25) -> float:
    """Raised-cosine day cycle mapped onto the run: ``trough`` at
    ``frac`` 0 and 1, ``peak`` at 0.5 — pure arithmetic on the virtual
    fraction, so it is deterministic by construction."""
    return trough + (peak - trough) * 0.5 * (1.0 - math.cos(
        2.0 * math.pi * frac))


def virtual_arrivals(rng, rate: float, phases: Phases, duration: float,
                     rate_fn: Optional[Callable[[float], float]] = None
                     ) -> Iterator[float]:
    """Seeded Poisson arrival times in VIRTUAL time — the phase
    multiplier and termination read virtual time only, so the offered
    sequence (arrival times + however many there are) is exactly
    (seed, trace, rate, duration)-determined; wall clock only paces
    the replay.  Exactly ONE rng.exponential per yielded arrival, so
    callers interleave their own size/payload draws off the same rng
    without perturbing the arrival sequence — both the request
    open-loop and the decode bench share this generator so their
    replay disciplines can never diverge.  ``rate_fn`` (e.g.
    :func:`diurnal_mult`) multiplies on top of the phase shape,
    making the instantaneous rate ``rate * mult_at(...) *
    rate_fn(frac)``."""
    t_virtual = 0.0
    while True:
        frac = t_virtual / duration
        r = rate * mult_at(phases, frac)
        if rate_fn is not None:
            r *= rate_fn(frac)
        t_virtual += rng.exponential(1.0 / r)
        if t_virtual >= duration:
            return
        yield t_virtual
