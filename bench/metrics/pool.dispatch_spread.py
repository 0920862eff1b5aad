"""pool.dispatch_spread: the most blocks any replica took in the window
over the mean blocks per replica (`ReplicaPool.n_dispatched`, read from
`describe_entry` before and after the window).  1.0 is perfect balance;
a replica starved by the router reads above it.  None for an entry that
is not a pool."""


def read(run):
    blocks = run.counters.get("n_dispatched")
    if not blocks or sum(blocks) == 0:
        return None
    return max(blocks) / (sum(blocks) / len(blocks))
