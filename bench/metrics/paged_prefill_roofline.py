"""The paged prefill kernel's share of its roofline in the traced window:
per layer and chunk, the larger of the causal attention operations over
the bf16 peak and the cached prefix, the chunk's rows, q and out over HBM
bandwidth, summed, over the kernel's device time in the trace, in
whatever program it ran, %."""
import counting
import readings

KERNELS = ("_paged_prefill_pallas",)


def read(run):
    peak, bw = run.peaks["bf16_flops"], run.peaks["hbm_bytes_per_s"]
    s = run.shape
    bound = 0.0
    for st in run.window_steps():
        for q0, c in st.prefill:
            cost = counting.prefill_attention(s, q0, c)
            bound += cost.bound_s(peak, bw)  # every layer alike
    return readings.share(bound, readings.kernel_seconds(run, KERNELS))
