"""Share of the mesh's row slots that held work over the traced slice: the
valid chunk rows the miner's mesh dispatches placed (``sweep.mesh_rows``)
over the devices times the rows of each dispatch's fullest device
(``sweep.mesh_row_slots``), in percent, from the fleet log.  100% when
every device of every dispatch sweeps as many rows as the fullest one;
25% on four devices when one device takes all rows.  None when the miner
counts no mesh dispatches."""


def read(ctx):
    c = (ctx.fleet or {}).get("counters", {})
    slots = c.get("sweep.mesh_row_slots", 0)
    if slots <= 0:
        return None
    return 100.0 * c.get("sweep.mesh_rows", 0) / slots
