"""Host time per trial of the evaluator's online path on W servers.

The program's ``prof.evaluate.online`` spans, one around each index
policy that ``evaluate`` scores on W > 1 servers (the index-table and
workload-cache lookups and the dynamic op call), less the
``prof.sojourn_eval.dynamic.*`` op spans.  In the cells that list it
every dynamic op call lies inside such a span, so what is left is the
evaluator's and the workload cache's own time.  Milliseconds per trial;
nothing when the program has no such span.
"""

SPAN = "evaluate.online"
OPS = "sojourn_eval.dynamic."


def read(ctx):
    online = ctx.spans.get(SPAN)
    if online is None or not ctx.trials:
        return None
    ops = sum(s for name, s in ctx.spans.items() if name.startswith(OPS))
    return (online - ops) / ctx.trials * 1e3
