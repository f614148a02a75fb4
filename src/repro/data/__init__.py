from repro.data.pipeline import (DataConfig, pipeline_stats, sample_batch,
                                 stacked_node_batches)
