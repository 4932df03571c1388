"""hop_tile_share: hop-loop tiles run over the tiles of the kernel's grid,
summed over the window's runtime calls: the program's
`Completion.tiles_run` over `Completion.tiles`, taken once per call (the
completions of one call share its `round` and `tier`).  1 means every
tile ran its hop loop; a tile of padding rows alone is skipped.  Nothing
where the program's completions carry no tile counts."""


def read(run):
    calls = {(c.round, c.tier): (c.tiles_run, c.tiles) for c in run.done
             if getattr(c, "tiles", None) is not None}
    if not calls:
        return None
    return (sum(r for r, _ in calls.values())
            / sum(t for _, t in calls.values()))
