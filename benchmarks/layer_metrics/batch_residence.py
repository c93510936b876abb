"""batch_residence (ms): mean time from a batch's dispatch start to the
end of its delivery loop (`digest.batch.residence_s`, one observation
per batch): dispatch, the wait behind `max_inflight`, collect,
deliver."""

import _spans


def read(ctx):
    return _spans.mean_ms(ctx, "digest.batch.residence_s")
