"""How long a request takes on the device to its first token: over the
requests whose first token a poll in the traced stretch delivered, the
mean of the end of the step that emitted it (``Request.first_token_step``,
placed by the program's per-window step→wall map) minus the moment its
prompt was written into the ring (``Request.ring_wall``), in milliseconds.
It holds the wait for admission and every prefill chunk."""
import statistics

from bench.harness import program_spans


def read(run):
    s = program_spans.stretch(run)
    if s is None:
        return None
    times = [end - ring for ring, end, _wall in program_spans.first_tokens(s)]
    if not times:
        return None
    return 1e3 * statistics.fmean(times), (
        f"{len(times)} first tokens, median "
        f"{1e3 * statistics.median(times):.4f} ms, max "
        f"{1e3 * max(times):.4f} ms; " + program_spans.remainder(run, s))
