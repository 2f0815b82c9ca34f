"""Model FLOPs of the training steps run in the traced window (forward and
backward, the LM head at the masked positions, no recomputation), over the
window at the chip's peak, in %."""


def read(rec):
    t = rec["trace"]
    if t["window_s"] <= 0 or not rec["work"]["train_flops"]:
        return None
    return 100.0 * rec["work"]["train_flops"] / (
        t["window_s"] * rec["peak"]["flops_per_s"])
