"""Per-primitive tile-size selection: measured-or-pinned, keyed by
shape signature.

Every primitive's free launch parameters (block sizes, row tiles) are
resolved here instead of baked in as constants.  Resolution order for
``tile_for(primitive, signature, defaults, ...)``:

1. **Pinned table** — ``PT_KERNEL_TILE_TABLE`` names a JSON file
   ``{primitive: {signature: {param: value}}}``; the signature ``"*"``
   pins a primitive-wide override.  Pinned entries are how tiles
   measured on the chip get carried to later runs without re-measuring
   (docs/KERNELS.md "Tile table"); unset = no table.
2. **Measured cache** — an in-process memo of previous autotune wins
   (one measurement per (primitive, signature) per process).
3. **Measured autotune** — when ``FLAGS_kernel_autotune`` is on AND the
   caller supplied ``candidates`` + a ``measure`` hook, each candidate
   is timed (one warm call to absorb compilation, one timed call) and
   the fastest wins; booked on ``pt_kernel_autotune_total{primitive}``.
4. **Defaults** — the primitive's built-in tiles (off by default: the
   autotune flag costs candidate compilations, so it is an explicit
   opt-in exactly like the reference's exhaustive-search autotuners).

A candidate dict only needs the params it overrides — the winner is
``defaults`` merged with the winning candidate, so partial pins work
("just the kv block").  A ``measure`` hook that raises for an invalid
candidate (tile too large for VMEM, shape indivisible) disqualifies
that candidate instead of failing the call.
"""

from __future__ import annotations

import json
import os
import time
from pathlib import Path

ENV_TABLE = "PT_KERNEL_TILE_TABLE"

_pinned = None      # lazy {primitive: {signature: {param: value}}}
_measured = {}      # {(primitive, signature): {param: value}}


def shape_signature(**dims):
    """Canonical signature string for a primitive call shape: sorted
    ``k=v`` pairs (``bh=8,d=64,s=256``) — stable across call sites so
    pinned tables written by one run resolve in another."""
    return ",".join(f"{k}={int(v)}" for k, v in sorted(dims.items()))


def _load_pinned():
    global _pinned
    if _pinned is None:
        _pinned = {}
        path = os.environ.get(ENV_TABLE, "")
        if path:
            try:
                table = json.loads(Path(path).read_text())
            except (OSError, ValueError) as e:
                raise ValueError(
                    f"{ENV_TABLE}={path!r} is not a readable JSON tile "
                    f"table ({{primitive: {{signature: {{param: value}}}}}}"
                    f"): {e}") from e
            if not isinstance(table, dict):
                raise ValueError(
                    f"{ENV_TABLE}={path!r}: top level must be an object "
                    f"keyed by primitive name")
            _pinned = table
    return _pinned


def clear_cache():
    """Forget the pinned table and measured wins (tests; also the hook
    for re-reading ``PT_KERNEL_TILE_TABLE`` after it changes)."""
    global _pinned
    _pinned = None
    _measured.clear()


def _autotune_enabled():
    from paddle_tpu.fluid import flags

    try:
        return bool(flags.flag("kernel_autotune"))
    except KeyError:  # pragma: no cover - flag table always has it
        return False


def _book(primitive, source):
    from paddle_tpu.observability import metrics as obs

    obs.counter(
        "pt_kernel_autotune_total",
        "Tile-table resolutions that did NOT come from primitive "
        "defaults: measured autotune wins and pinned-table hits, "
        "labeled by primitive and source (measured|pinned)",
        labels=("primitive", "source"),
    ).labels(primitive=primitive, source=source).inc()


def measure_candidates(candidates, measure):
    """Time each candidate via ``measure(candidate) -> None`` (one warm
    call, one timed call); returns ``(best_candidate, timings)`` where
    timings maps the candidate's repr to seconds (raising candidates
    are disqualified and recorded as None)."""
    best, best_t, timings = None, None, {}
    for cand in candidates:
        try:
            measure(cand)                       # warm: compile + cache
            # candidate micro-timing, not step/phase telemetry — the
            # winner is all that escapes this loop
            t0 = time.perf_counter()            # observability: allow
            measure(cand)
            dt = time.perf_counter() - t0       # observability: allow
        except Exception:
            timings[repr(cand)] = None          # disqualified candidate
            continue
        timings[repr(cand)] = dt
        if best_t is None or dt < best_t:
            best, best_t = cand, dt
    return best, timings


def tile_for(primitive, signature, defaults, candidates=None,
             measure=None):
    """Resolve the tile params for one primitive call.

    Returns a dict: ``defaults`` overlaid with the pinned / measured /
    autotuned values (callers index it — ``tile["block"]``)."""
    out = dict(defaults)
    table = _load_pinned().get(primitive, {})
    pinned = table.get(signature, table.get("*"))
    if pinned:
        out.update(pinned)
        _book(primitive, "pinned")
        return out
    cached = _measured.get((primitive, signature))
    if cached:
        out.update(cached)
        return out
    if candidates and measure is not None and _autotune_enabled():
        best, _ = measure_candidates(candidates, measure)
        if best is not None:
            _measured[(primitive, signature)] = dict(best)
            out.update(best)
            _book(primitive, "measured")
    return out
