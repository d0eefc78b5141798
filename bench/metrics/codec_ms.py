"""codec_ms: device milliseconds per round of the round's other
non-collective ops (encode, decode, apply), mean over the chips used."""


def read(t):
    return t.ms_per_round.get("codec") or None
