"""mesh: bytes the collectives of the dispatched mesh programs move
between chips, per answer: the program's counter `mesh.collective_bytes`
(from the operand shapes of every collective it traced, summed over the
shards, added at each dispatch) over the window's correct answers."""


def read(spans, counters, trace, window):
    moved = counters.get("obs.mesh.collective_bytes")
    if moved is None or not window.get("answered"):
        return None
    return moved / window["answered"]
