"""How unevenly the experts THIS chip holds were loaded in the run's last
step: the hottest held expert's rows over the held experts' mean, layer by
layer, averaged over the expert layers (1 = even; the grouped matmuls run
slower the higher it is). From the program's own device count,
``ctx["moe_expert_rows"]`` = ``[expert layers, experts held]``
(``engine.moe_expert_rows()``), no trace needed. None where the program
gives no row count or a layer drew no row."""


def read(ctx):
    rows = ctx.get("moe_expert_rows")
    if not rows or not all(sum(layer) for layer in rows):
        return None
    return sum(max(layer) * len(layer) / sum(layer) for layer in rows) / len(rows)
