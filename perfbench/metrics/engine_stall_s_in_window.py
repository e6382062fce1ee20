"""Seconds of engine-loop iterations of 250 ms or more inside the window
(``fusioninfer:engine_stall_seconds_total``; each such iteration logs one
``engine stall`` line in the server's log naming what held it).  Should
be 0.  Nothing on a program without the family."""


def read(run):
    return run.delta("fusioninfer:engine_stall_seconds_total")
