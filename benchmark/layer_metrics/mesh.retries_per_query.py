"""mesh: re-dispatches of a mesh program after a shard overflowed one of
its capacities (counter `mesh.retries`), per correct answer."""


def read(spans, counters, trace, window):
    retries = counters.get("obs.mesh.retries")
    if retries is None or not window.get("answered"):
        return None
    return retries / window["answered"]
