"""Share of the traced window in which the device was idle while the host
was inside a ``serve.forward`` span (dispatching the forward's ops and its
writeback), %: idle is the complement of the device's op intervals, as
``device_idle_share`` reads it."""
import spans


def read(run):
    sp = spans.read(run, __file__)
    if (sp is None or sp.idle_in_forward_s is None
            or not sp.named("serve.forward") or sp.window_s <= 0):
        return None
    return 100.0 * sp.idle_in_forward_s / sp.window_s
