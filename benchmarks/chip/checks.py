"""The comparison that decides a run's ``correct``.

After the window closes, a sample of the trials it scored, drawn from the
seed and spread evenly over the workload sets, is scored again by the
float64 reference (``reference.py``).  Each algorithm's answer is held to
the reference by its relative error, and the errors are gathered into
one number per kind of answer:

* ``optimal_rel_err``: OPTIMAL against the least value over all N! orders,
  so a wrong argmin shows as the gap between the chosen order and the best;
* ``static_rel_err``: RANK and RANDOM, through the static enumeration;
* ``dynamic_rel_err``: SR and SERPT, through the dynamic lockstep.

That is the mapping on one server.  A configuration whose file names
``n_servers`` = W > 1 is a cluster of W identical servers (the paper's
Section V): there RANK is the online RANK, a stage-level index policy
like SR and SERPT, and all three are scored by the reference's W-server
simulation and gathered into ``dynamic_rel_err``.  OPTIMAL and RANDOM are
static orders, whose optimality the paper proves for one server only; the
reference has no W-server answer for them, and :func:`numbers_of` refuses
them.

Each number is the largest over the sample and is compared with its limit
in ``limits.json``.  The control (:func:`control_numbers`) puts the same
reference, computed in bfloat16, in the program's place.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

import reference
import workgen

#: The number each algorithm's error is gathered into, on one server.
NUMBER_OF = {
    "optimal": "optimal_rel_err",
    "rank": "static_rel_err",
    "random": "static_rel_err",
    "sr": "dynamic_rel_err",
    "serpt": "dynamic_rel_err",
}
#: The same on W > 1 servers, where only index policies have a reference.
NUMBER_OF_SERVERS = {"rank": "dynamic_rel_err", "sr": "dynamic_rel_err",
                     "serpt": "dynamic_rel_err"}


def servers(config: dict) -> int:
    """The configuration's server count W: ``n_servers``, 1 when absent."""
    return config.get("n_servers", 1)


def numbers_of(config: dict, algorithms) -> dict[str, str]:
    """The number each algorithm's error is gathered into; raises
    ``ValueError`` for an algorithm the reference cannot score."""
    w = servers(config)
    table = NUMBER_OF if w == 1 else NUMBER_OF_SERVERS
    missing = [a for a in algorithms if a not in table]
    if missing:
        why = (f" on {w} servers, where only the index policies {sorted(table)} have one: "
               "static orders are optimal on one server only") if w > 1 else ""
        raise ValueError(f"no reference for {missing}{why}")
    return {a: table[a] for a in algorithms}


def sample(seed: int, n_done: int, count: int, n_sets: int) -> list[int]:
    """``count`` of the trials ``0..n_done-1``, drawn from the seed, every
    workload set in turn; all of them when there are no more."""
    if count >= n_done:
        return list(range(n_done))
    rng = workgen.stream(workgen.CHECK, seed)
    by_set = [rng.permutation(np.arange(s, n_done, n_sets)) for s in range(n_sets)]
    picks, i = [], 0
    while len(picks) < count:
        s, j = i % n_sets, i // n_sets
        if j < len(by_set[s]):
            picks.append(int(by_set[s][j]))
        i += 1
    return sorted(picks)


def reference_answers(config: dict, seed: int, trial: int, algorithms, dtype=np.float64):
    """What each algorithm should answer on trial ``trial`` of ``seed``, on
    the configuration's servers."""
    number_of = numbers_of(config, algorithms)
    sizes, probs = workgen.trial_group(config, seed, trial)
    n = len(sizes)
    out = {}
    for alg in algorithms:
        if number_of[alg] == "optimal_rel_err":
            out[alg] = float(np.min(reference.static_values(
                sizes, probs, reference.all_orders(n), dtype)))
        elif number_of[alg] == "static_rel_err":
            order = (reference.rank_order(sizes, probs) if alg == "rank"
                     else workgen.stream(workgen.RANDOM, seed, trial).permutation(n))
            out[alg] = float(reference.static_values(sizes, probs, order[None], dtype)[0])
        else:  # a stage-level index policy
            table = reference.index_table(sizes, probs, alg)
            out[alg] = reference.dynamic_value(sizes, probs, table, dtype, servers(config))
    return out


def _numbers(config, seed, trials, algorithms, answers_of) -> dict[str, float]:
    number_of = numbers_of(config, algorithms)
    numbers = {name: 0.0 for name in number_of.values()}
    for t in trials:
        want = reference_answers(config, seed, t, algorithms)
        got = answers_of(t)  # None: the trial raised, so it answered nothing
        for alg in algorithms:
            err = float("inf") if got is None else abs(float(got[alg]) - want[alg]) / abs(want[alg])
            if not np.isfinite(err):
                err = float("inf")
            name = number_of[alg]
            numbers[name] = max(numbers[name], err)
    return numbers


def program_numbers(config, seed, answers: list, algorithms, count: int) -> dict[str, float]:
    """The numbers compared, for the program's answers of a window.

    ``answers[t]`` is trial t's ``{algorithm: value}``.
    """
    trials = sample(seed, len(answers), count, len(config["workload_sets"]))
    return _numbers(config, seed, trials, algorithms, lambda t: answers[t])


def control_numbers(config, seed, n_done: int, algorithms, count: int) -> dict[str, float]:
    """The same numbers with the bfloat16 reference in the program's place."""
    trials = sample(seed, n_done, count, len(config["workload_sets"]))
    return _numbers(
        config, seed, trials, algorithms,
        lambda t: reference_answers(config, seed, t, algorithms, ml_dtypes.bfloat16),
    )
