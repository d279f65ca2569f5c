from .mesh import make_mesh, data_axes, dp_size, AXIS_ORDER
from .collectives import (allreduce, bucketed_allreduce, exchange_form,
                          leaf_allreduce, tree_allreduce, PushPullEngine,
                          psum_reducer)
