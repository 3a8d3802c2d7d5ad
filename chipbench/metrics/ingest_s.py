"""``ingest_s``: seconds in ``CompletionDataset(...)`` (shuffle, pad,
shard, device transfer), ended by ``block_until_ready``; host clock."""


def read(run):
    return run.host.get("ingest_s")
