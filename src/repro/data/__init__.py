from repro.data.causal_dgp import CausalData, make_causal_data  # noqa: F401
from repro.data.pipeline import ShardedFeed  # noqa: F401
