"""Open-loop client of `LLMServer`: submits each planned request when it is
due, steps the server whenever it has work, and records on the harness's
clock when each request was due, when it was submitted, and when each of
its tokens reached the client.

Host spans (`jax.profiler.TraceAnnotation`) mark the harness's own calls:
`bench.submit`, `bench.step` and `bench.wait` (idle, nothing to serve), so a
device trace's idle gaps can be put down to what the host was doing.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple

from jax.profiler import TraceAnnotation

from traffic.generate import Planned


@dataclass
class Record:
    """What the client saw of one request (times in seconds from the run's
    start, on the harness's clock)."""

    planned: Planned
    rid: str
    submit_s: Optional[float] = None
    token_s: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    finish_s: Optional[float] = None
    finish_reason: Optional[str] = None
    preempted: int = 0
    # per served token, the two largest log-probs the server reported
    top_logprobs: List[Tuple[float, float]] = field(default_factory=list)

    @property
    def due_s(self) -> float:
        return self.planned.due_s


@dataclass
class BatchCount:
    """Work of one non-empty scheduled micro-batch."""

    prefill_tokens: int
    decode_tokens: int
    sampled_rows: int
    attended_keys: int      # query-key pairs under the causal mask
    context_tokens: int     # keys each sequence's attention reads, summed


def count_batch(batch) -> BatchCount:
    """Count a `ScheduledBatch`: a prefill chunk of n tokens from position s
    attends to n*s + n(n+1)/2 keys and reads s + n; a decode row at
    position p attends to and reads p + 1."""
    attended = context = 0
    for seq in batch.prefill:
        s, n = seq.start_pos, seq.num_tokens
        attended += n * s + n * (n + 1) // 2
        context += s + n
    for seq in batch.decode:
        attended += seq.start_pos + 1
        context += seq.start_pos + 1
    return BatchCount(batch.num_prefill_tokens, batch.num_decode_tokens,
                      sum(1 for s in batch.seqs if s.produces_token),
                      attended, context)


class OpenLoop:
    """Drive `server` with `plan` (sorted by due time) from `t0`.  With
    `count_batches` every non-empty scheduled micro-batch is counted into
    `batches`, for the readers of a traced run."""

    def __init__(self, server, plan: Sequence[Planned], sampling_cls,
                 clock: Callable[[], float] = time.perf_counter,
                 tag: str = "bench", count_batches: bool = False) -> None:
        self.server = server
        self.plan = list(plan)
        self.sampling_cls = sampling_cls
        self.clock = clock
        self.records: List[Record] = [Record(p, f"{tag}-{p.index}")
                                      for p in self.plan]
        self.batches: List[Tuple[float, BatchCount]] = []
        # (start, seconds) of every `server.step()`, for the run's log
        self.steps: List[Tuple[float, float]] = []
        self.t0 = 0.0
        if count_batches:
            self._count_batches(server.replicas[0].scheduler)

    def _count_batches(self, sched) -> None:
        schedule = type(sched).schedule.__get__(sched)

        def counted(now: float = 0.0):
            batch = schedule(now)
            if not batch.is_empty:
                self.batches.append((self.clock() - self.t0,
                                     count_batch(batch)))
            return batch
        sched.schedule = counted

    def _sink(self, rec: Record):
        def sink(delta) -> None:
            now = self.clock() - self.t0
            if delta.event == "preempt":
                rec.preempted += 1
            if delta.token is not None:
                rec.token_s.append(now)
                rec.tokens.append(delta.token)
            if delta.finish_reason is not None:
                rec.finish_s = now
                rec.finish_reason = delta.finish_reason
        return sink

    def _submit(self, rec: Record) -> None:
        p = rec.planned
        with TraceAnnotation("bench.submit"):
            self.server.subscribe(rec.rid, self._sink(rec))
            self.server.submit(p.prompt, self.sampling_cls(
                max_new_tokens=p.max_new_tokens, temperature=p.temperature),
                request_id=rec.rid)
        rec.submit_s = self.clock() - self.t0

    def abort_open(self) -> None:
        """Abort every submitted request that has not finished, and run the
        server until it is idle."""
        for r in self.records:
            if r.submit_s is not None and r.finish_reason is None:
                self.server.abort(r.rid)
        self.server.drain()

    def run(self, t0: float, stop_s: float,
            events: Sequence[Tuple[float, Callable[[], None]]] = ()) -> None:
        """Serve until `stop_s` seconds after `t0`.  Each `(t, fn)` of
        `events` runs once, at the first loop turn at or after `t`."""
        self.t0 = t0
        pending = sorted(events, key=lambda e: e[0])
        i, n = 0, len(self.records)
        while True:
            now = self.clock() - t0
            if now >= stop_s:
                return
            while pending and pending[0][0] <= now:
                pending.pop(0)[1]()
            while i < n and self.records[i].due_s <= now:
                self._submit(self.records[i])
                i += 1
            if self.server.has_work:
                began = self.clock() - t0
                with TraceAnnotation("bench.step"):
                    self.server.step()
                self.steps.append((began, self.clock() - t0 - began))
                continue
            wake = min([stop_s]
                       + ([self.records[i].due_s] if i < n else [])
                       + ([pending[0][0]] if pending else []))
            with TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, wake - now))


def attach_logprobs(server, records: Sequence[Record]) -> None:
    """Copy into each record the log-probs the server reported per token."""
    for r in records:
        r.top_logprobs = list(server.get(r.rid).top_logprobs)


def ttfts_s(records: Sequence[Record], end_s: float) -> List[float]:
    """Time to first token of each record, from its due time; a request
    with no first token by `end_s` counts the time it has waited."""
    out = []
    for r in records:
        first = r.token_s[0] if r.token_s and r.token_s[0] <= end_s else end_s
        out.append(first - r.due_s)
    return out


def itls_s(records: Sequence[Record], start_s: float,
           end_s: float) -> List[float]:
    """Every gap between consecutive tokens of a request, both tokens
    reaching the client inside [start_s, end_s]."""
    out = []
    for r in records:
        ts = [t for t in r.token_s if start_s <= t <= end_s]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def tokens_in(records: Sequence[Record], start_s: float,
              end_s: float) -> int:
    """Output tokens that reached the client inside [start_s, end_s]."""
    return sum(1 for r in records for t in r.token_s if start_s <= t <= end_s)
