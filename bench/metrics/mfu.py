"""mfu: model FLOPs of one round (forward and backward, no recompute;
``bench/flops.py``) over the traced round time times the chips times the
chips' bf16 peak, in percent."""


def read(t):
    round_s = t.window_s / t.rounds
    return 100.0 * t.flops_per_round / (round_s * t.chips
                                        * t.peaks["bf16_flops"])
