"""Share of the window the engine's coordinating thread spent in its own
stages: the union of its ``decide``, ``tune.*``, ``retire`` and
``refine`` spans over the window's length."""
import collections

STAGES = ("decide", "tune", "retire", "refine")


def read(run):
    decides = collections.Counter(s.tid for s in run.spans
                                  if s.name == "decide")
    span = run.t_end - run.t_start
    if not decides or span <= 0:
        return None
    coord = decides.most_common(1)[0][0]
    busy, end = 0.0, -float("inf")
    for s in sorted((s for s in run.spans if s.tid == coord
                     and s.name.split(".", 1)[0] in STAGES),
                    key=lambda s: s.t_start):
        lo = max(s.t_start, end)
        if s.t_end > lo:
            busy += s.t_end - lo
        end = max(end, s.t_end)
    return busy / span
