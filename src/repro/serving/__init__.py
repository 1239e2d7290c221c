from .engine import (  # noqa: F401
    Engine,
    Request,
    ServeStats,
    plan_batch_size,
)
from .kv import (  # noqa: F401
    DEFAULT_BLOCK_SIZE,
    BlockAllocator,
    BlockOOM,
    block_words,
    plan_pool_blocks,
    prefix_chain,
)
