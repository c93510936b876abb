"""deliver_busy (s/s): host seconds per wall second in the delivery loop
of a collected batch — digest to callback; on `plain` that is digest to
reply record to encoder (`span.digest.deliver.seconds`)."""

import _spans


def read(ctx):
    return _spans.busy(ctx, "span.digest.deliver.seconds")
