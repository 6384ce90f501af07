"""Share of the rows given to the held experts' grouped products that no
token was routed to, in %: 1 - sum of ``moe_routed_rows`` over sum of
``moe_buffer_rows``, the engine's per-round counters (summed over each
round's real steps and its MoE layers), over the window's rounds.  Traced
runs only; nothing where the program books no such counter."""


def read(rec):
    if rec.spans is None:
        return None
    routed = buffer = 0.0
    for s in rec.spans:
        if s[0] == "C" and s[1] == "moe_routed_rows":
            routed += s[3]
        elif s[0] == "C" and s[1] == "moe_buffer_rows":
            buffer += s[3]
    if buffer <= 0:
        return None
    return 100.0 * (1.0 - routed / buffer)
