"""Share of the traced window in which no operation ran on the device, in
%: 1 - the union of the device-op intervals inside the runner's ``traced``
span over that span, averaged over the chips (``device.busy_s`` and
``device.window_s`` of the result line).  It is the trace's own reading,
of the traced steps or seconds: the profiler slows the host loop, so a
host-bound cell idles more here than outside the trace."""


def read(run: dict):
    t = run["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
