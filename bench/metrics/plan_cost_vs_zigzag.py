"""Mean, over the run's fixed quality sample of searched requests, of the
plan's comm cost over the zigzag deployment's on the same graph, both from
the plain evaluator."""


def read(run):
    rows = [run.rows.get(i) for i in run.sample]
    if not rows or any(r is None for r in rows):
        return None
    return sum(r["cost"] / r["zigzag"] for r in rows) / len(rows)
