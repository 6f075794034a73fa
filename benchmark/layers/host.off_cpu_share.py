"""host process: of the self time of the spans that are the program's own
Python, the share in which their thread did not run, in percent: over the
window's traces, 1 - sum of `self_cpu_ms` / sum of `self_ms`. Such a span
neither parks nor waits for the device, so what its thread did not run it
stood runnable without the interpreter lock, or behind one of the
program's own locks. Left out: the spans that wait by their nature
(`sched.wait`, `batch.hold`, `engine.device_wait`, `cdc.tail`) and any
span without `self_cpu_ms`. The two sums are taken whole and their
difference clipped at 0, not each span's: where the host's CPU clock
ticks (10 ms on the TPU hosts of PERF.md's runs) a span reads 0 or a
whole tick, and only the sums mean anything. None where no span has a
`self_cpu_ms`."""

NAMES = frozenset((
    "request", "parse", "plan.compile", "executor.fanout", "topn.rank",
    "topn.chunk", "topn.replay", "device.dispatch", "batch.launch",
    "engine.memo_probe", "engine.stack", "gather"))


def read(ctx):
    ran = total = 0.0
    for t in ctx.traces:
        for s in t.get("spans", ()):
            if s["name"] in NAMES and "self_cpu_ms" in s and "self_ms" in s:
                total += s["self_ms"]
                ran += s["self_cpu_ms"]
    return 100.0 * max(0.0, total - ran) / total if total else None
