"""(token, slot) pairs routed to the experts held here, per window token
and expert layer: the executor's `moe_routed_pairs` summed over the
window, over the window's tokens times the expert layers. Even routing
reads top_k x held / routed (6 x 8 / 64 = 0.75).

None for a program that does not count its routed pairs."""


def read(run):
    steps = run["steps"]
    if not steps or not all("moe_routed_pairs" in s for s in steps):
        return None
    cfg = run["cfg"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return sum(s["moe_routed_pairs"] for s in steps) / (
        run["tokens"] * layers)
