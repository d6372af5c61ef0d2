"""Share of a PCG set's device time spent on the solver's own vector
work: the ops under ``packsell.solver_vec`` (dots, norms, axpys, the
Jacobi step, the history update) or ``packsell.stored_permute`` (the
σ-permutes of b, the Jacobi diagonal and x). With ``spmv_pct.pcg`` it
covers the set."""

VECTOR_SCOPES = ("packsell.solver_vec", "packsell.stored_permute")


def read(ctx):
    t = ctx["trace"]
    if ctx["kind"] != "pcg_sets":
        return None
    total = t.op_time_s()
    part = t.op_time_s(VECTOR_SCOPES)
    if total <= 0 or part <= 0:
        return None
    return 100.0 * part / total
