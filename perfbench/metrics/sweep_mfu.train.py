"""The whole sweep's share of the chip's peak: the least time of the
window's algorithmic work (alias build plus chain, ``work.sweep``) at the
chip's peaks over the traced window's time (%).  LDA does almost no
matrix arithmetic, so bandwidth bounds it."""


def read(run):
    return 100.0 * run.work["sweep"].least_seconds(run.peaks) \
        / run.trace.window_s
