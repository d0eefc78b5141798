"""local_step_ms: device milliseconds per round of the local step (ops under
automatic differentiation, and the local-iteration scan), mean over the
chips used."""


def read(t):
    return t.ms_per_round.get("local_step") or None
