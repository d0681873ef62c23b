"""HBM-tier hits over all lookups in the window, from the server's exact
counters (``stats()["hbm"]["augmented"]["hbm_hits"]`` and the per-form
serve counts), in %.  Nothing to read without an HBM tier."""


def read(run):
    before, after = run.stats_before, run.stats_after
    if "hbm" not in after:
        return None
    hits = (after["hbm"]["augmented"]["hbm_hits"]
            - before["hbm"]["augmented"]["hbm_hits"])
    a = after["telemetry"]["serve_counts"]
    b = before["telemetry"]["serve_counts"]
    lookups = sum(a.values()) - sum(b.values())
    if lookups <= 0:
        return None
    return 100.0 * hits / lookups
