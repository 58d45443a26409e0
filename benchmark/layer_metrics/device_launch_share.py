"""`device_compute` as the host spent it, the launching part: the sum
of history `device.launch.<kernel>` (the exclusive seconds of
`dispatch_span` and of the join kernels' launch scopes: Python, pack,
enqueue; counter `stream_device_host_seconds{kernel, stage="launch"}`),
over the span of `stage_span.py`. With `device_wait_share` it is
`phase.device_compute` over the same span. A program that writes no
`device.launch.*` reads nothing."""

from stage_span import share


def read(record):
    return share(record, lambda k: k.startswith("device.launch."))
