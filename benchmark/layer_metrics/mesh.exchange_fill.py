"""mesh: how full the exchange slots of the partitioned joins ran: the
settled jobs' worst destination occupancy (counter
`mesh.exchange_rows_max`) over the slots a destination had (counter
`mesh.exchange_slots`), both summed over the jobs of the window, in
percent.  Power-of-two slots sized near a destination's share read
50-100; a reading near 0 says the slots are sized far past what
arrives, and every pass over them pays for it.  Nothing where no
partitioned join settled (or the program has no such counters)."""


def read(spans, counters, trace, window):
    slots = counters.get("obs.mesh.exchange_slots")
    if not slots:
        return None
    return 100.0 * counters.get("obs.mesh.exchange_rows_max", 0) / slots
