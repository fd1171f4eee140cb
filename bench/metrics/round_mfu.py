"""round_mfu: analytic forward and backward FLOPs of the client steps run
in the window, over window seconds x chips x the chip's bf16 peak."""


def read(run):
    steps = sum(1 for t in run.client_steps if run.in_window(t))
    peak = run.peaks.get("bf16_flops")
    if not steps or not peak or run.window_s <= 0:
        return None
    return 100.0 * steps * run.flops_per_step / (
        run.window_s * run.chips * peak)
