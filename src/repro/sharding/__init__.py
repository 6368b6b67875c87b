from repro.sharding.specs import (OP_CLASS_AXES, named_sharding_tree,
                                  batch_sharding, replicated,
                                  plan_from_degrees, degree_to_axes,
                                  clamp_degree_for_axis, validate_plan)
from repro.sharding.collective_matmul import (ag_matmul, ag_matmul_rs,
                                              matmul_rs)

__all__ = ["OP_CLASS_AXES", "named_sharding_tree", "batch_sharding",
           "replicated", "plan_from_degrees", "degree_to_axes",
           "clamp_degree_for_axis", "validate_plan", "ag_matmul",
           "ag_matmul_rs", "matmul_rs"]
