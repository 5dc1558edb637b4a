"""The CAMP GEMM kernels' share of their roofline in the traced window:
the least time of every GEMM call the window's steps made (int8 peak for
the operations, HBM for the bytes, each call bounded on its own), over
the device time of the GEMM kernels in the trace, in whatever program
they ran, %."""
import counting
import readings

KERNELS = ("camp_gemm_fused_", "camp_gemm_w4", "camp_gemm_a4w4",
           "camp_gemm_i8")


def read(run):
    peak, bw = run.peaks["int8_ops"], run.peaks["hbm_bytes_per_s"]
    bound = 0.0
    for st in run.window_steps():
        bound += counting.camp_gemm_bound_s(
            run.shape, len(st.decode_lengths), len(st.decode_lengths),
            peak, bw)
        for i, (q0, c) in enumerate(st.prefill):
            last = i == len(st.prefill) - 1 and st.prefill_done
            bound += counting.camp_gemm_bound_s(run.shape, c, int(last),
                                                peak, bw)
    return readings.share(bound, readings.kernel_seconds(run, KERNELS))
