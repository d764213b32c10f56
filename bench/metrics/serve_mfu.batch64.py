"""Model operations of the prompt and output tokens the window processed
over the window times the chip's bf16 peak: `serve_mfu.batch`'s reading,
counted by the Moonlight configuration's family."""
from bench.cells import metric_reader

read = metric_reader("serve_mfu.batch")
