from .executor import (PermuteCall, PermuteProgram,  # noqa: F401
                       compile_program, programs_for_topology,
                       schedules_for_topology)
from .collectives import (tree_all_gather, tree_all_reduce,  # noqa: F401
                          tree_all_to_all, tree_broadcast, tree_reduce,
                          tree_reduce_scatter)
from .mesh_axes import CollectiveContext, AxisSchedules  # noqa: F401
from .overlap import BucketedAllReduce  # noqa: F401
