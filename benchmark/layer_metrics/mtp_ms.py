"""Device time a step in the multi-token-prediction depth: every layer
whose key starts with `mtp`, of every type (`mtp`, its norms, attention
and expert layer, and the shared head's second pass, `lm_head.mtp_head`),
both directions. Loops are counted once (`scope_leaf`'s table, made from
`scope_sub`'s one compile). Silent on a net without such a layer."""

from benchmark import scope_sub


def read(obs):
    keys = {l.name for l in obs.net.layers
            if l.type != "fullc" and l.name.startswith("mtp")}
    if not keys:
        return None
    table = scope_sub.leaf_table(obs)
    if table is None:
        return None
    rows = [ns for (phase, _, key), ns in table.ns.items()
            if phase in ("fwd", "bwd") and key in keys]
    return sum(rows) / table.steps / 1e6 if rows else None
