"""Model FLOP utilisation of the engine step: the operations of the useful
work done in the traced windows (prompt tokens prefilled and tokens
decoded, 2 per weight each, the output head once per emitted token, and
attention over each token's actual context) divided by the summed window
times and the chip's bf16 peak. Padded lanes and padded chunk rows do not
count, so this cannot read above what the chip did."""


def read(run):
    wall = sum(w.wall for w in run.windows)
    if wall <= 0 or run.work.useful_flops <= 0:
        return None
    return 100.0 * run.work.useful_flops / (wall * run.peaks["bf16_flops"])
