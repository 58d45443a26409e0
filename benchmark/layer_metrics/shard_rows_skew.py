"""Rows the fullest shard received from the window's exchanges over the
rows a shard received on average, from the history's
`mesh_exchange.shard_rows.<shard>`, window sums per shard. 1 is an even
split; the mesh width is one shard receiving everything."""

PREFIX = "mesh_exchange.shard_rows."


def read(record):
    by_shard = {}
    for h in record["history"].values():
        for name, rows in h.items():
            if name.startswith(PREFIX):
                by_shard[name] = by_shard.get(name, 0.0) + rows
    if not by_shard or not sum(by_shard.values()):
        return None
    return max(by_shard.values()) * len(by_shard) / sum(by_shard.values())
