"""Closed loop over a backlog: ``window`` clients of the engine each wait
for their reply before the next request goes out.

The engine takes no arrivals while ``run()`` is draining its queue, so
the backlog is handed over ``requests_per_run`` at a time and each
``run()`` serves them with the engine's window full until the last few
drain.  Those drains are part of the measured window.
"""


def drive(window, traffic: dict) -> None:
    k = int(traffic["requests_per_run"])
    while not window.over():
        window.serve(k)
