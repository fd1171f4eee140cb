from repro.data.synthetic import (SiloDataset, audio_stream,
                                  lm_batch_iterator, make_silo_datasets,
                                  synthetic_lm_batch)

__all__ = ["SiloDataset", "make_silo_datasets", "lm_batch_iterator",
           "synthetic_lm_batch", "audio_stream"]
