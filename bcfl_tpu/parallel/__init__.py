from bcfl_tpu.parallel import gspmd  # noqa: F401
from bcfl_tpu.parallel.ring_attention import (  # noqa: F401
    ring_attention,
    ring_attention_gspmd,
    ring_attention_sharded,
)
from bcfl_tpu.parallel.fed_tp import (  # noqa: F401
    build_fed_tp_round,
    stack_adapters,
)
from bcfl_tpu.parallel.sp import (  # noqa: F401
    init_sp_lm,
    make_sp_lm_train_step,
    ring_config,
    ring_override,
)
