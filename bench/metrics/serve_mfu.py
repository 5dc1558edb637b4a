"""Model operations of every token computed in the traced window (prompt
tokens not served from shared pages, and output tokens: every layer's
GEMMs, attention and the head where it ran) over the traced window times
the chip's int8 peak, %."""
import counting


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    s = run.shape
    ops = 0.0
    for st in run.window_steps():
        b = len(st.decode_lengths)
        if b:
            ops += counting.model_ops(
                s, b, b, counting.decode_attention(s, st.decode_lengths))
        for i, (q0, c) in enumerate(st.prefill):
            last = int(i == len(st.prefill) - 1 and bool(st.prefill_done))
            ops += counting.model_ops(
                s, c, last, counting.prefill_attention(s, q0, c))
    if ops == 0:
        return None
    return 100.0 * ops / (run.trace.window_s * run.peaks["int8_ops"])
