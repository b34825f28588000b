"""What the harness asks of a serving engine, and what it gets back.

An engine is the file ``<bench>/engines/<kind>.py``, ``kind`` the
configuration's ``engine.kind`` (``concurrent`` when the key is absent).
The file defines ``OWN_CHIPS``, True when the chips its engine serves
on are the harness process's own (the harness then lets metrics run
set-up work on them: ``setup(run)`` of a metric file; otherwise the
harness process starts no JAX backend at all), and ``open(config,
traffic, *, trace, work)``: the cell's configuration and traffic mix,
whether the run is traced, and a directory the engine may write its
profiler output under.  ``open`` returns an engine that offers, in
this order:

  ``chips()``          the chips it serves on, as ``Chip``\\ s; the
                       harness holds their platform and number to the
                       cell's.
  ``setup(buckets, tenants) -> picks``
                       fills its tuning cache as the traffic mix asks
                       and warms every shape the window can use;
                       ``buckets`` maps ``(program, rows)`` to a
                       request's ``(chunked, shared)`` host arrays,
                       ``tenants`` are the mix's tenant names, and
                       ``picks`` maps each bucket to the
                       ``(partitions, tasks)`` split it was tuned to.
  ``begin()``          the window starts: spans and compile stamps are
                       kept from here.
  ``serve(requests) -> [Served]``
                       serves one batch of ``Request``\\ s and returns
                       when every one has retired.
  ``start_profile()``, ``stop_profile()``
                       the profiler on every chip it holds, around part
                       of the window.
  ``collect() -> Collected``
                       after the window: spans of every process, compile
                       stamps, each chip's memory peak and trace.
  ``close()``          stops every process and thread it started.

Times are ``time.perf_counter`` seconds: on one host that is one clock
across processes, so the harness compares stamps from any of them.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional

DEFAULT_KIND = "concurrent"


def kind_of(config: dict) -> str:
    """The engine file a configuration names."""
    return config["engine"].get("kind", DEFAULT_KIND)


@dataclasses.dataclass
class Chip:
    platform: str         # as JAX names it: "tpu", "cpu"
    kind: str             # JAX's ``device_kind``


@dataclasses.dataclass
class Request:
    key: int              # the harness's name for it inside one batch
    program: str
    tenant: str
    chunked: dict         # host arrays, split by rows
    shared: dict          # host arrays every chunk reads whole
    check: bool           # its outputs come back for the reference check


@dataclasses.dataclass
class Served:
    key: int
    ok: bool
    split: tuple          # (partitions, tasks) it ran under; (0, 0) if none
    t_pop: float          # taken off the engine's queue
    t_retire: float       # retired
    in_bytes: int
    out_bytes: int
    outputs: Optional[list] = None   # host arrays, one per kernel call,
    #                                  for a request sent with ``check``


@dataclasses.dataclass
class Span:
    """One closed span of the program, tagged with its process: ``tid``
    is ``(proc, thread)``, so that readers keyed by thread never mix two
    processes' threads."""

    name: str
    t_start: float
    t_end: float
    proc: int
    tid: tuple
    parent: Optional[str] = None
    depth: int = 0
    attrs: Optional[dict] = None

    @property
    def duration_s(self) -> float:
        return self.t_end - self.t_start


def spans_of(records) -> list:
    """The program's span records as ``Span``\\ s of this process."""
    proc = os.getpid()
    return [Span(name=r.name, t_start=r.t_start, t_end=r.t_end, proc=proc,
                 tid=(proc, r.tid), parent=r.parent, depth=r.depth,
                 attrs=r.attrs) for r in records]


@dataclasses.dataclass
class Trace:
    """One chip's profile: ``events`` as ``devtrace.load`` gives them
    (its ``anchor_ns`` on the trace's clock), ``anchor`` the same
    instant on the host clock, and ``proc`` the process whose spans
    name the chip's idle gaps."""

    events: dict
    anchor: float
    proc: int


@dataclasses.dataclass
class Collected:
    spans: list           # ``Span``\\ s opened since ``begin()``
    compile_times: list   # host-clock stamps of backend compiles
    memory_peaks: list    # bytes, one per chip
    traces: list          # ``Trace``\\ s, one per chip profiled
