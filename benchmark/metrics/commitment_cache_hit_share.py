"""commitment_cache_hit_share: the share of blob commitment lookups over
the window that an earlier pass had computed (hits over hits and misses
of the program's blob_commitment_lookups_total counter). Each blob is
looked up by CheckTx, the proposer's filter and ProcessProposal, so a
node that admitted every blob reads 2/3: in the produce cells the
traffic fixes it there, and a lower reading means the memo is broken.
None where the program does not keep that counter, or looked nothing
up."""

COUNTER = "blob_commitment_lookups_total"


def read(run):
    kept = any(key.partition("{")[0] == COUNTER for key in run.counters1)
    lookups = run.counter_delta(COUNTER) if kept else 0.0
    if not lookups:
        return None
    return run.counter_delta(COUNTER, result="hit") / lookups
