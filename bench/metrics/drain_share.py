"""Share of the window the engine's coordinating thread (the thread
with the most ``decide`` spans) spent blocked until its worker pool was
empty: its ``engine.drain`` spans over the window's length.  They never
nest in one another, so their sum is their union."""
import collections


def read(run):
    decides = collections.Counter(s.tid for s in run.spans
                                  if s.name == "decide")
    span = run.t_end - run.t_start
    if not decides or span <= 0:
        return None
    coord = decides.most_common(1)[0][0]
    drains = [s.duration_s for s in run.spans
              if s.tid == coord and s.name == "engine.drain"]
    return sum(drains) / span if drains else None
