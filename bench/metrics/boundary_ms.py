"""Host time at each window boundary: the benchmark's own span around
``BlinkServer.run_window`` minus the server's timing of the device window
(``window_wall``), in milliseconds, averaged over the traced windows. It
holds the DPU-plane work (submission flush, token poll, window re-launch)
that a request waits through between windows."""


def read(run):
    ws = run.windows
    if not ws:
        return None
    return 1e3 * sum(w.end - w.start - w.wall for w in ws) / len(ws)
