"""Row slots the window's all_to_alls carried (launches x n_dev x n_dev
x bucket: rows plus padding) over the rows routed through them, from
the history's `mesh_exchange.slots_carried` and
`mesh_exchange.rows_routed`, window sums. 1 is an exchange without
padding; the mesh width (4) is every shard receiving a whole batch's
worth of slots."""


def read(record):
    epochs = record["history"].values()
    routed = sum(h.get("mesh_exchange.rows_routed", 0.0) for h in epochs)
    if not routed:
        return None
    return sum(h.get("mesh_exchange.slots_carried", 0.0)
               for h in epochs) / routed
