"""The producer's time blocked on the chip per round: the engine's
``wait.generator`` spans (the host's fetch of the data generator's output,
which queues on the device behind the round in flight), summed over the
window's rounds, over the rounds, in ms.  Traced runs only; nothing where
the program books no such span."""


def read(rec):
    if rec.spans is None or not rec.rounds:
        return None
    waits = [s[3] for s in rec.spans if s[0] == "X" and s[1] == "wait.generator"]
    if not waits:
        return None
    return 1e3 * sum(waits) / len(rec.rounds)
