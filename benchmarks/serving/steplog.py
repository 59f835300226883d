"""A span around each call into the session's step loop.

`StepLog.wrap(session)` replaces the session's ``step`` with a call that
times the original (host clock), writes a ``bench.step`` host span into
the profiler's trace when one is being taken, and records what the step
did from the rows the session holds before and after it:

    live        rows that held a request during the step
    wide        whether a row was still streaming its prompt (chunked
                prefill), which widens the fused step
    tokens      tokens the step fed to the model (prompt chunk + decode)
    prompt      of which prompt tokens
    ctx         sum over fed tokens of the context each attends
    kv          sum over rows that did work of their resident tokens after
                the step (what attention had to read)
    gather_s    host bookkeeping the paged state counted in the step

Row contents are read from the session's ``_rows`` (per row: tokens
already resident, ``prefilled`` while the prompt streams, ``plen +
len(outs) - 1`` once decoding); a session that lacks them stops the run
with an error naming what is missing, rather than leave metrics out.
"""
from __future__ import annotations

import dataclasses
import time

import jax


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    live: int | None
    wide: bool | None
    tokens: int | None
    prompt: int | None
    ctx: float | None
    kv: float | None
    gather_s: float | None
    emitted: int


def _rows(session):
    """{id(row): (row, kind, resident tokens)}."""
    rows = getattr(session, "_rows", None)
    if rows is None:
        raise RuntimeError("the session has no `_rows` to read the step's "
                           "rows from")
    out = {}
    try:
        for a in rows:
            if a is None:
                continue
            if a.pending is not None:
                out[id(a)] = (a, "p", int(a.prefilled))
            else:
                out[id(a)] = (a, "d", int(a.plen) + len(a.outs) - 1)
    except AttributeError as e:
        raise RuntimeError(f"a session row lacks what the step log "
                           f"reads: {e}") from e
    return out


def _span(before: int, after: int) -> float:
    """Sum of contexts of tokens at positions before .. after - 1 (each
    attends itself and everything before it)."""
    n = after - before
    return n * before + n * (n + 1) / 2


class StepLog:
    def __init__(self):
        self.steps: list[Step] = []

    @property
    def count(self) -> int:
        return len(self.steps)

    def wrap(self, session):
        inner = session.step
        page_tokens = session.pool.page_tokens

        def step():
            before = _rows(session)
            state = session.state
            g0, ad0 = state.gather_s, session.pages_adopted_total
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                events = inner()
            t1 = time.perf_counter()
            after = _rows(session)
            g1 = state.gather_s
            emitted = sum(len(ev.tokens) for ev in events)
            rec = Step(t0, t1, None, None, None, None, None, None,
                       g1 - g0, emitted)
            adopted = (session.pages_adopted_total - ad0) * page_tokens
            self._fill(rec, before, after, adopted)
            self.steps.append(rec)
            return events

        session.step = step
        return self

    @staticmethod
    def _fill(rec: Step, before: dict, after: dict, adopted: int):
        live = wide = 0
        tokens = prompt = 0
        ctx = kv = 0.0
        for key in set(before) | set(after):
            b, a = before.get(key), after.get(key)
            if b is None:                        # admitted in this step
                row, kind_a, res_a = a
                res_b, kind_b = 0, "p"
            elif a is None:                      # finished in this step
                row, kind_b, res_b = b
                res_a = res_b + 1 if kind_b == "d" else int(row.plen)
                kind_a = "d"
            else:
                row, kind_b, res_b = b
                _, kind_a, res_a = a
            live += 1
            if kind_b == "p":
                wide = 1
            n = res_a - res_b
            if n <= 0:
                continue                         # waited for its chunk
            tokens += n
            if kind_b == "p":
                prompt += n
            ctx += _span(res_b, res_a)
            kv += res_a
        # prefix pages adopted at admission were not computed here
        tokens -= adopted
        prompt -= adopted
        rec.live, rec.wide = live, bool(wide)
        rec.tokens, rec.prompt, rec.ctx, rec.kv = tokens, prompt, ctx, kv


def in_window(steps, t0: float, t1: float):
    """Steps that started inside [t0, t1)."""
    return [s for s in steps if t0 <= s.t0 < t1]
