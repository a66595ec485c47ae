"""``broker_grids_per_call``: Real grids per engine call of the fleet
broker (BrokerStats), what-if cells."""
from benchlib.readers import broker_grids_per_call


def read(run):
    return broker_grids_per_call(run)
