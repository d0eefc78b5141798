"""codec_roofline: the protocol's least HBM bytes per round
(``bench/flops.codec_least_bytes``) at the chip's peak bandwidth, over the
codec's device time, in percent.  Bound by bandwidth: the codec does next to
no FLOPs."""


def read(t):
    ms = t.ms_per_round.get("codec")
    if not ms:
        return None
    return 100.0 * t.least_bytes / t.peaks["hbm_bw"] / (ms / 1e3)
