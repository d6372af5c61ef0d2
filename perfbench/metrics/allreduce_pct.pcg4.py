"""Share of a distributed PCG set's device time spent in the all-reduces
of its dots and norms: the ops under the program's ``packsell.allreduce``
scope (the ``psum`` of each local partial; the scope nests inside
``packsell.solver_vec``), averaged over the chips. A program without the
scope reads None."""


def read(ctx):
    t = ctx["trace"]
    if ctx["kind"] != "pcg_sets_dist":
        return None
    total = t.op_time_s()
    part = t.op_time_s(("packsell.allreduce",))
    if total <= 0 or part <= 0:
        return None
    return 100.0 * part / total
