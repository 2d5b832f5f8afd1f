"""Process start to the start of the window: weights made on the device,
the engine built, every shape of the cell warmed up (compilation, or
loading from the persistent cache, included).  Host clock."""
UNIT, SOURCE = "s", "host_clock"


def read(run):
    return run.setup_s
