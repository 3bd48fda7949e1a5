"""The whole extended step's share of the chip's peak: the operations a
plain forward and backward pass over the batch requires (from the
shapes; recomputation and the extensions' extra work do not count), per
extended step of the traced window, over peak bf16 FLOP/s."""


def read(r):
    if r.steps <= 0 or r.step_s <= 0:
        return None
    return 100.0 * r.step_flops / (r.step_s / r.steps) / r.peak_flops
