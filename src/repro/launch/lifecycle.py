"""Request lifecycle for the serving engine: states, records, deadlines.

The de-specialization thesis applied to *failure* shapes: one request
abstraction has to survive every way a request can leave the engine,
not just the happy path.  A request moves through

::

    QUEUED ──> RUNNING ──> COMPLETED
       │          │ ├────> CANCELLED   (cancel(req_id))
       │          │ ├────> TIMED_OUT   (deadline passed at a block boundary)
       │          │ ├────> FAILED      (device fault lane, no recovery path)
       │          │ └────> PREEMPTED ──> QUEUED   (pages spilled to host)
       ├────────> CANCELLED
       └────────> TIMED_OUT

Every terminal transition returns whatever tokens the request committed
so far (``Engine.results[req_id]``) instead of raising — exceptions are
reserved for caller errors (bad input at ``submit``) and for genuinely
unrecoverable engine faults.  ``PREEMPTED`` is the one non-terminal
exit: the request's pages are copied to host memory and it re-enters
the queue carrying its full restart state (position, held token,
partial outputs, drafting history, spilled page payloads, recurrent
lane), so resumption is a restore, never a recompute.
"""

from __future__ import annotations

import enum
import time

import numpy as np

__all__ = ["RequestStatus", "TERMINAL_STATUSES", "PriorityClass",
           "coerce_priority", "normalize_slo_targets",
           "normalize_class_quotas", "validate_request", "request_row"]


class RequestStatus(str, enum.Enum):
    """Where a request is in its lifecycle (str-valued for JSON/stats)."""

    QUEUED = "queued"
    RUNNING = "running"
    PREEMPTED = "preempted"
    COMPLETED = "completed"
    CANCELLED = "cancelled"
    TIMED_OUT = "timed_out"
    FAILED = "failed"


#: statuses a request never leaves
TERMINAL_STATUSES = frozenset({
    RequestStatus.COMPLETED, RequestStatus.CANCELLED,
    RequestStatus.TIMED_OUT, RequestStatus.FAILED,
})


class PriorityClass(enum.IntEnum):
    """SLO class of a request — a *scheduling* property, never a
    sampling one (the same prompt yields the same tokens in every
    class; only admission order, victim order and shed budget differ).

    Lower value = more important.  The ordering is load-bearing in
    three places: the admission queue serves the lowest-valued
    non-empty class first (FIFO within a class), preempt-and-spill
    ranks victims by *descending* value (BATCH pages spill before a
    REALTIME request ever loses one), and SLO-driven shedding
    sacrifices the budgets that serve high-valued classes first.
    """

    REALTIME = 0
    STANDARD = 1
    BATCH = 2


def coerce_priority(value) -> PriorityClass:
    """Accept a :class:`PriorityClass`, its int value, or its name
    (any case); reject everything else with the valid choices named.

    ``None`` means "caller didn't say" and maps to STANDARD — the
    middle class, so defaulted traffic neither starves batch work nor
    jumps ahead of explicitly-realtime requests.
    """
    if value is None:
        return PriorityClass.STANDARD
    if isinstance(value, PriorityClass):
        return value
    if isinstance(value, str):
        try:
            return PriorityClass[value.strip().upper()]
        except KeyError:
            raise ValueError(
                f"unknown priority class {value!r} (choices: "
                f"{[c.name.lower() for c in PriorityClass]})") from None
    if isinstance(value, (int, np.integer)) and not isinstance(value, bool):
        try:
            return PriorityClass(int(value))
        except ValueError:
            raise ValueError(
                f"priority class value {int(value)} out of range "
                f"(valid: {[int(c) for c in PriorityClass]})") from None
    raise ValueError(
        f"priority must be a PriorityClass, its name or its int value "
        f"(got {type(value).__name__})")


def normalize_slo_targets(targets) -> dict:
    """Validate per-class SLO targets into ``{PriorityClass: {...}}``.

    ``targets`` maps a class (enum / name / int, via
    :func:`coerce_priority`) to ``{"ttft_s": s, "tok_per_s": r}``;
    either key may be absent or ``None`` (no target on that axis).
    A non-positive target is rejected like a non-positive
    ``deadline_s`` — it could never be met, so it is always a caller
    bug, and a zero TTFT target would make every queued request
    "at risk" forever (permanent shedding).
    """
    out = {}
    for key, tgt in (targets or {}).items():
        cls = coerce_priority(key)
        if tgt is None:
            continue
        if not isinstance(tgt, dict):
            raise ValueError(
                f"SLO target for {cls.name.lower()} must be a dict "
                f"with 'ttft_s'/'tok_per_s' keys (got "
                f"{type(tgt).__name__})")
        unknown = set(tgt) - {"ttft_s", "tok_per_s"}
        if unknown:
            raise ValueError(
                f"unknown SLO target keys {sorted(unknown)} for "
                f"{cls.name.lower()} (valid: ttft_s, tok_per_s)")
        clean = {}
        for k in ("ttft_s", "tok_per_s"):
            v = tgt.get(k)
            if v is None:
                continue
            if float(v) <= 0:
                raise ValueError(
                    f"SLO {k} for class {cls.name.lower()} must be "
                    f"positive (got {v})")
            clean[k] = float(v)
        if clean:
            out[cls] = clean
    return out


def normalize_class_quotas(quotas) -> dict:
    """Validate per-class page-pool quotas into
    ``{PriorityClass: {"floor": f, "cap": f}}``.

    ``quotas`` maps a class (enum / name / int, via
    :func:`coerce_priority`) to ``{"floor": fraction, "cap": fraction}``:

    * ``floor`` *reserves* that fraction of the pool — other classes may
      never allocate into it, so the class always has room to admit
      (the REALTIME working-set guarantee);
    * ``cap`` *bounds* the fraction the class may occupy at admission
      (a soft cap: it blocks new allocations, it never evicts running
      requests when traffic shifts — the BATCH-flood limiter).

    Fractions must lie in (0, 1]: zero is a no-op spelled as a
    guarantee, above one can never be satisfied.  The floors must sum
    to at most 1 (you cannot reserve more than the pool), and a floor
    above the same class's cap is contradictory (the class could never
    fill its own reservation).
    """
    out: dict = {}
    total_floor = 0.0
    for key, quota in (quotas or {}).items():
        cls = coerce_priority(key)
        if quota is None:
            continue
        if not isinstance(quota, dict):
            raise ValueError(
                f"class quota for {cls.name.lower()} must be a dict "
                f"with 'floor'/'cap' keys (got {type(quota).__name__})")
        unknown = set(quota) - {"floor", "cap"}
        if unknown:
            raise ValueError(
                f"unknown class-quota keys {sorted(unknown)} for "
                f"{cls.name.lower()} (valid: floor, cap)")
        if cls in out:
            raise ValueError(
                f"duplicate class quota for {cls.name.lower()} "
                f"(the same class named twice under different spellings)")
        clean = {}
        for k in ("floor", "cap"):
            v = quota.get(k)
            if v is None:
                continue
            v = float(v)
            if not 0.0 < v <= 1.0:
                raise ValueError(
                    f"class-quota {k} for {cls.name.lower()} must lie in "
                    f"(0, 1] (got {v}): 0 is a no-op spelled as a "
                    f"guarantee, above 1 can never be satisfied")
            clean[k] = v
        if ("floor" in clean and "cap" in clean
                and clean["floor"] > clean["cap"]):
            raise ValueError(
                f"class-quota floor {clean['floor']} above cap "
                f"{clean['cap']} for {cls.name.lower()}: the class could "
                f"never fill its own reservation")
        total_floor += clean.get("floor", 0.0)
        if clean:
            out[cls] = clean
    if total_floor > 1.0 + 1e-9:
        raise ValueError(
            f"class-quota floors sum to {total_floor:.3f} > 1: cannot "
            f"reserve more than the whole pool")
    return out


def validate_request(prompt, *, vocab: int, temperature=None, top_k=None,
                     deadline_s=None, priority=None) -> np.ndarray:
    """Admission-time input validation; returns the prompt as int32.

    Garbage that used to flow straight into the embedding gather is
    rejected at the API boundary instead:

    * non-integer token ids (a float array with fractional values would
      silently truncate to different tokens than the caller sent),
    * out-of-vocab ids (negative, or >= vocab: the gather would read a
      neighbouring row — worse than an error, a *wrong answer*),
    * negative ``temperature`` (<= 0 means greedy by engine convention,
      but a negative value is always a caller bug: it would flip the
      distribution toward the *least* likely tokens),
    * negative ``top_k`` (0 disables the filter; negative has no
      meaning),
    * non-positive ``deadline_s`` (the request could never run), and
    * unknown ``priority`` classes (a typo'd class name or an
      out-of-range value would silently schedule the request in a
      class the caller never meant — see :func:`coerce_priority`).

    ``temperature``/``top_k``/``deadline_s`` accept the same
    scalar-or-``{slot: v}`` forms ``add_requests`` does; every value is
    checked individually (``None`` entries mean "no limit" and are
    skipped, never compared).  Collapsing a dict to one representative
    — an earlier revision validated ``min(deadline_s.values())`` — is
    exactly the specialization bug this layer exists to prevent: it
    crashes on mixed ``None`` entries and hides which request was
    invalid.
    """
    p = np.asarray(prompt)
    if p.ndim > 1:
        p = p.reshape(-1)
    if p.size and not np.issubdtype(p.dtype, np.integer):
        if not (np.issubdtype(p.dtype, np.floating)
                and np.all(np.isfinite(p)) and np.all(p == np.floor(p))):
            raise ValueError(
                f"prompt token ids must be integers (got dtype {p.dtype} "
                f"with non-integral values); refusing to truncate")
    p = p.astype(np.int64, copy=False)
    if p.size and (int(p.min()) < 0 or int(p.max()) >= vocab):
        bad = p[(p < 0) | (p >= vocab)][0]
        raise ValueError(
            f"prompt contains out-of-vocab token id {int(bad)} "
            f"(vocab={vocab}); the embedding gather would read garbage")

    def each(v, name):
        vals = v.values() if isinstance(v, dict) else [v]
        for x in vals:
            if x is None:
                continue
            yield name, x

    for name, x in each(temperature, "temperature"):
        if float(x) < 0:
            raise ValueError(
                f"negative temperature {x} (0 = greedy; negative would "
                f"invert the sampling distribution)")
    for name, x in each(top_k, "top_k"):
        if int(x) < 0:
            raise ValueError(f"negative top_k {x} (0 disables the filter)")
    for name, x in each(deadline_s, "deadline_s"):
        if float(x) <= 0:
            raise ValueError(f"deadline_s must be positive (got {x})")
    for name, x in each(priority, "priority"):
        coerce_priority(x)          # unknown/out-of-range classes raise
    return p.astype(np.int32)


def request_row(*, ttft_s: float, gen_tokens: int, decode_s: float,
                status: RequestStatus, priority=None,
                queue_s: float = 0.0, req_id=None) -> dict:
    """One ``Engine.request_log`` row for a retired request.

    ``queue_s`` is the time the request waited in the admission queue,
    from submit to the admission call that took it (0 for a direct
    slot-addressed add); ``ttft_s`` counts from submit too.  ``req_id``
    is the request's id, the key of its ``Engine.results`` entry.

    ``tok_per_s`` is ``None`` — not ``0.0`` — when the decode interval
    is not measurable (``decode_s == 0`` under fake clocks, or a request
    that finished within the clock's resolution): a literal zero would
    read as a stalled request and drag throughput means toward zero, so
    aggregates must *skip* unmeasurable rows rather than average them.

    ``priority`` lands as the class *name* (``"realtime"`` /
    ``"standard"`` / ``"batch"``) so rows stay JSON-serializable like
    ``status``; per-class percentile aggregation keys on it.
    """
    return {"id": req_id, "ttft_s": float(ttft_s),
            "queue_s": float(queue_s),
            "gen_tokens": int(gen_tokens),
            "decode_s": float(decode_s), "status": status.value,
            "priority": coerce_priority(priority).name.lower(),
            "tok_per_s": (gen_tokens / decode_s) if decode_s > 0
            else None}


def now() -> float:
    """Engine wall clock (monkeypatchable seam for deadline tests)."""
    return time.perf_counter()
