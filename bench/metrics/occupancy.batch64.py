"""Mean share of the decode slots the engine decoded per block, over the
window's decode blocks: `occupancy.batch`'s reading."""
from bench.cells import metric_reader

read = metric_reader("occupancy.batch")
