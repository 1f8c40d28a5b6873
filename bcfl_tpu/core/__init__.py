from bcfl_tpu.core.mesh import (  # noqa: F401
    ClientMesh,
    client_mesh,
    distributed_init,
    fed_tp_mesh,
    pod_client_mesh,
    pod_devices,
)
from bcfl_tpu.core.prng import (  # noqa: F401
    client_key_data,
    client_round_keys,
    fold_round,
)
