from .api import parallelize_experts, moe_plan
from .layer import MoEConfig, MoEMLP
from .experts_allocator import ExpertsAllocator, BasicExpertsAllocator
from .token_dispatcher import TokenDispatcher
from .moe_param_buffer import MoEParamBuffer
from .moe_optimizer import MoEOptimizer
from .dropless import dropless_experts, route_group_limited, route_topk
