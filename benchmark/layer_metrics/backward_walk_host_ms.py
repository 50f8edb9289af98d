"""gluon loop: median host milliseconds per step of
``autograd.backward`` outside the nodes' vjp programs: self time of the
span ``autograd/backward/walk`` (tape walk, cotangents, gradient
write-out), its ``autograd/backward/dispatch`` children left out."""
import spanread


def read(data):
    return spanread.median_ms_per_step(data, ("autograd/backward/walk",),
                                       self_time=True)
