"""`compiles_in_window.serve`: traces of the simulator core inside the
window (``barrier_sim.core_traces()`` delta); every shape is warmed in
set-up, so this should read 0."""


def read(r: dict):
    c = r.get("counters", {})
    return float(c["compiles"]) if "compiles" in c else None
