"""Host time per trial in the evaluator and workload cache.

The seconds spent inside ``evaluate_many`` that no ``prof.sojourn_eval.*``
span of the program covers: the evaluator's own work, the workload
cache's tables, OPTIMAL's permutations.  Milliseconds per trial.
"""


def read(ctx):
    if not ctx.trials:
        return None
    ops = sum(s for name, s in ctx.spans.items() if name.startswith("sojourn_eval."))
    return (ctx.eval_s - ops) / ctx.trials * 1e3
