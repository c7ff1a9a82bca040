"""engine.host_ms_per_step: host time of one ``Engine.step`` outside its
fenced device dispatches, in ms. The harness's clock around each step
minus the ``measured_s`` of the step's tick events, mean over the steps of
the window that ran without the profiler."""


def read(ctx):
    fenced = {}
    for t in ctx.ticks:
        fenced[t.step] = fenced.get(t.step, 0.0) + t.measured_s
    host = [t1 - t0 - fenced.get(i, 0.0)
            for i, (t0, t1) in enumerate(ctx.window.steps)
            if i not in ctx.profiled]
    return sum(host) / len(host) * 1e3 if host else None
