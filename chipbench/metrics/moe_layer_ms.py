"""Device milliseconds a decode launch spends in the MoE layers: the ops of
the decode program traced under any of the five MoE scopes (``moe_route``,
``moe_dispatch``, ``moe_experts``, ``moe_combine``, ``shared_expert``;
``models/moe.py``), summed over every MoE layer, over the window's decode
launches. Nothing where the program has no such scope."""
import scopes

MOE_SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine",
              "shared_expert")


def read(r):
    ops = [o for o in r.trace.ops if o.module.startswith("jit_decode")]
    if not ops or not r.decodes:
        return None
    table = scopes.op_names(scopes.decode_text(r.cell))
    ns = 0.0
    for op in ops:
        path = scopes.scope_of(op, table)
        if path and any(scopes.under(path, s) for s in MOE_SCOPES):
            ns += op.dur
    return ns * 1e-6 / len(r.decodes) if ns else None
