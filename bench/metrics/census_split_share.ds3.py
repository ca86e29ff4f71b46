"""census_split_share.ds3: the candidate x workload evaluations of the
traced window's campaigns that the program priced with a census split (its
``evaluator_split_evals_total`` counter) over all of them
(``evaluator_candidates_total``), in %; None where the driver read no such
counters."""


def read(obs):
    counters = obs.get("counters") or {}
    total = counters.get("evaluator_candidates_total")
    split = counters.get("evaluator_split_evals_total")
    if not total or split is None:
        return None
    return split / total * 100.0
