"""batch_fill (ms): mean wait of a batch's OLDEST item from its submit to
the batch's dispatch start (`digest.batch.fill_s`, one observation per
batch; behind a hub the clock starts in the session's queue)."""

import _spans


def read(ctx):
    return _spans.mean_ms(ctx, "digest.batch.fill_s")
