"""The producer's own host work per round: the engine's ``prep.*`` spans
less the ``wait.generator`` spans nested in them (barrier, refit, sample,
planning, packing, H2D dispatch), summed over the window's rounds, over
the rounds, in ms.  Traced runs only; nothing where the program books no
``wait.generator`` span."""


def read(rec):
    if rec.spans is None or not rec.rounds:
        return None
    spans = [s for s in rec.spans if s[0] == "X"]
    waits = [s[3] for s in spans if s[1] == "wait.generator"]
    if not waits:
        return None
    prep = sum(s[3] for s in spans if s[1].startswith("prep."))
    return 1e3 * (prep - sum(waits)) / len(rec.rounds)
