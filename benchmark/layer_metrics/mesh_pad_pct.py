"""parallel/mpp on a mesh: the share of the positions the sharded
program runs that are padding. Every shard of the clustered stream is
cut at a key-run edge and padded to one length (`_clustered_splits`,
`_row_bucket`), and the program's stream-long gathers pay for every
position. Over the window's `mpp.launch` spans: 100 x (shards x
shard_len - sum of shard_rows) / (shards x shard_len), from the span's
own args. Source: program_span. A program whose launches do not say how
the stream lies over the mesh (the args are absent), and the cop path,
read nothing."""


def read(ctx):
    positions = rows = 0
    for e in ctx["events"]:
        a = e["args"]
        if e["name"] == "mpp.launch" and a.get("shard_rows") and a.get("shard_len"):
            positions += a.get("shards", len(a["shard_rows"])) * a["shard_len"]
            rows += sum(a["shard_rows"])
    if not positions:
        return None
    return 100.0 * (positions - rows) / positions
