from fedtpu.parallel.mesh import make_mesh, client_sharding, CLIENTS_AXIS  # noqa: F401
from fedtpu.parallel.round import build_round_fn, init_federated_state  # noqa: F401
from fedtpu.parallel import ring  # noqa: F401  (explicit ppermute ring schedules)
from fedtpu.parallel import tp  # noqa: F401  (2-D clients x model engine)
from fedtpu.parallel import async_fed  # noqa: F401  (FedBuff-style async engine)
# fedtpu.parallel.ring_pallas is NOT imported eagerly: it pulls jax pallas
# machinery; import it directly where needed.
