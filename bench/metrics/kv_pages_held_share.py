"""Share of the KV page pool that live requests hold, in percent: pages
allocated over pages in the pool, as the engine's own allocator counts
them after each decode step that ended in the window, averaged over
those steps. What the deployment reserves and the traffic leaves
unused shows as the rest."""


def read(run):
    held = [(a, n) for t, _, a, n in run.log.pools
            if run.t0 <= t < run.end and n > 0]
    if not held:
        return None
    return 100.0 * sum(a / n for a, n in held) / len(held)
