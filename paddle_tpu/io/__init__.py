"""paddle_tpu.io — Dataset/DataLoader/samplers (reference: python/paddle/io).

TPU-native DataLoader notes: the accelerator consumes whole batches via a single
device_put (host->HBM over PCIe); prefetching overlaps host collate with
device compute. Multi-process workers use the same worker-pool design as the
reference's _DataLoaderIterMultiProcess (io/dataloader/dataloader_iter.py:370) with
an in-memory queue instead of LoDTensorBlockingQueue shared memory.
"""

from .dataset import (  # noqa: F401
    ChainDataset,
    ComposeDataset,
    ConcatDataset,
    Dataset,
    IterableDataset,
    Subset,
    TensorDataset,
    random_split,
)
from .dataloader import (  # noqa: F401
    DataLoader,
    DataLoaderWorkerError,
    default_collate_fn,
    get_worker_info,
)
from .sampler import (  # noqa: F401
    BatchSampler,
    DistributedBatchSampler,
    RandomSampler,
    Sampler,
    SequenceSampler,
    SubsetRandomSampler,
    WeightedRandomSampler,
)
