"""host process: CPU time of the whole server process (`host.cpu_s`:
every thread, the runtime's and the collector's included) per query the
server admitted over the window, in ms. To be read beside 1 / `ops_per_s`
(the wall time an answer costs) and `device.busy_ms_per_op`: the larger of
interpreter time and device time an answer is the limit. A traced window
holds the 5 s capture, whose CPU is in it. None where the program has no
such group."""


def read(ctx):
    cpu_s = ctx.delta("host", "cpu_s")
    answers = ctx.delta("scheduler", "admitted")
    if cpu_s is None or not answers:
        return None
    return 1000.0 * cpu_s / answers
