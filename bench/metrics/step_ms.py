"""Engine step time: the summed device-window times (``window_wall``,
host clock around the window program and ``block_until_ready``) of the
traced windows, divided by the steps they ran, in milliseconds."""


def read(run):
    ws = run.windows
    if not ws:
        return None
    return 1e3 * sum(w.wall for w in ws) / (len(ws) * run.serve.window)
