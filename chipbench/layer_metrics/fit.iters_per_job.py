"""Solver iterations of a job (model.n_iter_ / model.num_iters): the guard that
the work of a fit stayed what the configuration states."""


def read(ctx):
    jobs = getattr(ctx, "jobs", None)
    if not jobs:
        return None
    return sum(j["iters"] for j in jobs) / len(jobs)
