"""The paged decode kernel's share of its roofline in the traced window:
per layer and step, the larger of the attention operations over the bf16
peak and the cached int8 rows, scales, q and out over HBM bandwidth,
summed, over the kernel's device time in the trace, in whatever program
it ran, %."""
import counting
import readings

KERNELS = ("_paged_attention_pallas",)


def read(run):
    peak, bw = run.peaks["bf16_flops"], run.peaks["hbm_bytes_per_s"]
    s = run.shape
    bound = 0.0
    for st in run.window_steps():
        if st.decode_lengths:
            c = counting.decode_attention(s, st.decode_lengths)
            bound += c.bound_s(peak, bw)     # every layer alike
    return readings.share(bound, readings.kernel_seconds(run, KERNELS))
