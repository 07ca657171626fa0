"""prefill_programs (count, harness): distinct prefill programs (dispatch
widths) the warm-up ran, one per width the cell's traffic dispatches."""


def read(ctx):
    return ctx.prefill_programs
