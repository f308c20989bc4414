"""A test-only family: the dense family, with every call of the interface
recorded in ``CALLS``, to show that the cell drivers reach a family only
through ``model.family`` and use all of it."""
import model as bmodel

dense = bmodel.load_family("dense")
CALLS = set()


def _recorded(name):
    def call(*args, **kw):
        CALLS.add(name)
        return getattr(dense, name)(*args, **kw)
    return call


for _name in bmodel.INTERFACE + ("make_params",):
    globals()[_name] = _recorded(_name)
