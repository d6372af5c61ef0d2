"""Share of a distributed PCG set's device time spent in the halo
exchange: the ops under the program's ``packsell.halo`` scope (the send
takes, the ``ppermute`` rounds and the receive scatters of each matvec),
averaged over the chips. A program without the scope reads None."""


def read(ctx):
    t = ctx["trace"]
    if ctx["kind"] != "pcg_sets_dist":
        return None
    total = t.op_time_s()
    part = t.op_time_s(("packsell.halo",))
    if total <= 0 or part <= 0:
        return None
    return 100.0 * part / total
