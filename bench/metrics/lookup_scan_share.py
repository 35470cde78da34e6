"""The share of the lookup kernel's grid tiles whose compute ran, over the
window's batches: the kernel's own count of tiles not skipped by early
exit (LookupResult.tiles), summed, over the tiles in the grids. None where
the lookup reports no count."""


def read(run):
    w = run.window
    ran = grid = 0
    for rec in run.records:
        tiles = getattr(rec.res, "tiles", None)
        if tiles is not None and w.t_open <= rec.t < w.t_close:
            ran += tiles[0]
            grid += tiles[1]
    return 100.0 * ran / grid if grid else None
