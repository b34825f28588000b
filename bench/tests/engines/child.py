"""A stub engine whose chip belongs to a child process: the
``concurrent`` engine, run in a process of its own pinned to chip 0 (the
fleet's ``chip_env``), driven over a pipe.

It stands for an engine such as a fleet of one-chip workers: the
harness's process starts no JAX backend, and the spans, traces, compile
stamps and memory peaks come back from the child, each span and trace
tagged with the child's process.  The benchmark's tests copy it into a
checkout as ``bench/engines/child.py``; a run on the chip copies it the
same way.

    python3 child.py <bench> <read fd> <write fd>

is the child: it answers ``(method, args, kwargs)`` frames with
``(error, value)`` frames until ``close``, or until the pipe closes.
"""
from __future__ import annotations

import os
import pickle
import subprocess
import sys
import traceback
from pathlib import Path

#: the chip is the child's
OWN_CHIPS = False


def open(config: dict, traffic: dict, *, trace: bool, work: Path):
    return Child(config, traffic, trace=trace, work=work)


class Child:
    def __init__(self, config: dict, traffic: dict, *, trace: bool,
                 work: Path):
        from repro.serving.fleet.router import chip_env

        bench = Path(__file__).resolve().parents[1]
        up_r, up_w = os.pipe()          # child -> harness
        down_r, down_w = os.pipe()      # harness -> child
        self.proc = subprocess.Popen(
            [sys.executable, __file__, str(bench), str(down_r), str(up_w)],
            pass_fds=(down_r, up_w), stdout=2,
            env={**os.environ, **chip_env(0)})
        os.close(down_r)
        os.close(up_w)
        self._recv = os.fdopen(up_r, "rb")
        self._send = os.fdopen(down_w, "wb")
        self._call("open", config, traffic, trace=trace, work=work)

    def _call(self, method: str, *args, **kw):
        pickle.dump((method, args, kw), self._send)
        self._send.flush()
        from harness import BenchError

        try:
            error, value = pickle.load(self._recv)
        except EOFError:
            raise BenchError(f"engine child {self.proc.pid} exited with "
                             f"{self.proc.wait()}") from None
        if error is not None:
            kind, text = error
            raise (BenchError if kind == "BenchError" else RuntimeError)(
                f"engine child {self.proc.pid}: {text}")
        return value

    def chips(self):
        return self._call("chips")

    def setup(self, buckets: dict, tenants: list) -> dict:
        return self._call("setup", buckets, tenants)

    def begin(self):
        return self._call("begin")

    def serve(self, requests: list) -> list:
        return self._call("serve", requests)

    def start_profile(self):
        return self._call("start_profile")

    def stop_profile(self):
        return self._call("stop_profile")

    def collect(self):
        return self._call("collect")

    def close(self) -> None:
        try:
            self._call("close")
        finally:
            self._send.close()
            self._recv.close()
            self.proc.wait(timeout=120)


def serve_pipe(bench: Path, recv, send) -> None:
    """The child's loop: one engine of kind ``concurrent``."""
    import harness

    harness.point_compile_cache(bench)
    opener = harness.load_plugin(bench, "engines", "concurrent")
    engine = method = None
    while True:
        try:
            method, args, kw = pickle.load(recv)
        except EOFError:              # the harness has gone
            break
        error = value = None
        try:
            if method == "open":
                engine = opener.open(*args, **kw)
            else:
                value = getattr(engine, method)(*args, **kw)
        except Exception as e:  # noqa: BLE001 — sent to the harness
            error = (type(e).__name__, traceback.format_exc())
        pickle.dump((error, value), send)
        send.flush()
        if method == "close":
            break
    if engine is not None and method != "close":
        engine.close()


if __name__ == "__main__":
    BENCH = Path(sys.argv[1])
    # in place of this file's directory, whose concurrent.py would
    # shadow the standard library's package
    sys.path[0:1] = [str(BENCH / "lib"), str(BENCH.parent / "src")]
    with os.fdopen(int(sys.argv[2]), "rb") as r, \
            os.fdopen(int(sys.argv[3]), "wb") as w:
        serve_pipe(BENCH, r, w)
