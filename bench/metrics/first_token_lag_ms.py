"""How long a first token waits on the device before the host reads it:
over the requests whose first token a poll in the traced stretch
delivered, the mean of the token's arrival at the reader
(``Request.first_token_wall``) minus the end of the step that emitted it
(``Request.first_token_step``, placed by the program's per-window
step→wall map), in milliseconds. With tokens read only at window
boundaries this is the rest of the window plus the boundary's work up to
the poll."""
import statistics

from bench.harness import program_spans


def read(run):
    s = program_spans.stretch(run)
    if s is None:
        return None
    lags = [wall - end for _ring, end, wall in program_spans.first_tokens(s)]
    if not lags:
        return None
    return 1e3 * statistics.fmean(lags), (
        f"{len(lags)} first tokens, median "
        f"{1e3 * statistics.median(lags):.4f} ms, max "
        f"{1e3 * max(lags):.4f} ms; " + program_spans.remainder(run, s))
