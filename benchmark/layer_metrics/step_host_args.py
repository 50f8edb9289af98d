"""train step: leaves of the step call's arguments that were not arrays
already on the step's device or mesh (Python and numpy scalars, numpy
arrays, host-CPU arrays), per call, from the deltas of
``mxnet_step_host_arg_leaves`` in the dispatch span's records.  Each is
a copy made inside the call.  A count: it repeats exactly."""
import spanread


def read(data):
    return spanread.counter_per_step(data, "mxnet_step_host_arg_leaves",
                                     spanread.DISPATCH, per_call=True)
