"""Routed token-expert rows over the rows the ragged kernel computes in
its used tiles (128 a tile), in percent, over the window's MoE layer
calls (program counter: ``SparseMoE.expert_stats``, read after the
window)."""


def read(record):
    c = record["counters"]
    if not c.get("moe_computed_rows"):
        return None
    return 100.0 * c["moe_routed_rows"] / c["moe_computed_rows"]
