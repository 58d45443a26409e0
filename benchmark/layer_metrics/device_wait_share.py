"""`device_compute` as the host spent it, the waiting part: the sum of
history `device.wait.<kernel>` (the ready-wait of `jaxtools.fetch`, the
host standing still, under the label of the dispatch it stood in;
counter `stream_device_host_seconds{kernel, stage="wait"}`), over the
span of `stage_span.py`. Near the device's busy share of the window
where the host waits for real work. A program that writes no
`device.wait.*` reads nothing."""

from stage_span import share


def read(record):
    return share(record, lambda k: k.startswith("device.wait."))
