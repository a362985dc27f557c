"""check_tx_ante_ms: CheckTx's own work per block, ms: the app.check_tx
spans less their blob.validate children, i.e. the tx decode, the ante
chain (signature, fee, sequence, gas) and the check-state write. Beside
blob_validate_ms it splits what CheckTx costs inside mempool_admit_ms."""


def read(run):
    if not run.stats.get("blocks") or not run.spans_named("app.check_tx"):
        return None
    t = run.self_time(("app.check_tx",), ("blob.validate",))
    return t * 1e3 / run.stats["blocks"]
