"""hop_useful_share: hops that expanded a node over hops the hop loop ran,
summed over every request of the window: the program's `Completion.hops`
over `Completion.hops_run` (padded rows of a batch have no completion, so
they are left out).  1 means every hop of the budget found a frontier; the
rest is work an early exit would save.  Nothing where the program's
completions carry no hops."""


def read(run):
    rows = [(c.hops, c.hops_run) for c in run.done
            if getattr(c, "hops", None) is not None]
    if not rows:
        return None
    return sum(h for h, _ in rows) / sum(r for _, r in rows)
