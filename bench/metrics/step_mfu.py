"""step_mfu (%): the model's forward and backward FLOPs per step, as the
configuration counts them from its shapes (K-FAC work and recomputation
left out), times the steps per second of the traced window, over the
chip's bf16 peak (the program's float32 matmuls run as one bf16 pass)."""
import peaks


def value(ctx):
    if not ctx["steps"] or ctx["window_s"] <= 0:
        return None
    peak = peaks.peaks_for(ctx["device_kind"])["bf16_flops"]
    chips = ctx["cell"].chips
    rate = ctx["built"].flops_per_step * ctx["steps"] / ctx["window_s"]
    return 100.0 * rate / (peak * chips)
