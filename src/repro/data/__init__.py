from repro.data.pipeline import DataConfig, sample_batch, stacked_node_batches
