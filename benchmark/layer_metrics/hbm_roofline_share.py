"""hbm_roofline_share — how near the kernels run to the memory roofline.

layer: kernels (ops/csr); source: device_trace; moves: qps. The least
bytes the traced span's answered requests must move
(the kinds module's ``least_bytes``) over the chip's HBM bandwidth
(``benchmark/peaks.PEAKS``), over the device busy time of the span, in
percent. The bound is memory: these statements do integer compares and
adds, a few operations per byte."""


def read(obs):
    trace = obs.get("trace")
    if trace is None or trace["busy_s"] <= 0 or obs["least_bytes_in_trace"] <= 0:
        return None
    from benchmark import peaks

    peak = peaks.peak_for(obs["device_kind"])
    least_s = obs["least_bytes_in_trace"] / peak["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
