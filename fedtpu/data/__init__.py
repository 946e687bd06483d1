from fedtpu.data import partition
from fedtpu.data.datasets import (
    data_source,
    dataset_info,
    is_token_dataset,
    load,
)
from fedtpu.data.augment import augment_batch

__all__ = ["partition", "load", "dataset_info", "data_source", "augment_batch",
           "is_token_dataset"]
