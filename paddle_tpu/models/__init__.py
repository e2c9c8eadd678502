"""Built-in model zoo (analog of reference benchmark/fluid/models/ and the
book-chapter models under python/paddle/fluid/tests/book/). Each model is a
function from input Variables to (loss/prediction) Variables built with
paddle_tpu.layers — the same graph-building contract as the reference."""
from . import resnet  # noqa: F401
from . import mnist  # noqa: F401
from . import vgg  # noqa: F401
from . import alexnet  # noqa: F401
from . import googlenet  # noqa: F401


# The block files written for serving. Each states its model once:
# <Family>Config, `Config` for short; spec_from_config(cfg), the
# DecodeSpec with the parameter names; language_model_logits(tokens,
# cfg), which builds the whole-sequence program from that spec and
# leaves the description on it (describe_served_model); and the spec's
# paged_logits, the same block walk over a page pool
# (models/transformer.build_paged_prefill_program). A saved program
# names its family here, and the DecodeTranspiler looks the module up
# by that name: a new family is a block file and a name in this tuple.
SERVED_FAMILIES = ('hybrid', 'nemotron_h', 'axk1', 'granite_h',
                   'smallthinker', 'solar_open2', 'sdar_moe', 'lfm2')


def describe_served_model(program, family, cfg):
    """Leave on `program` what `family`'s block file built it from: the
    Config's fields, which Config(**fields) takes back."""
    assert family in SERVED_FAMILIES, family
    program.served_model = {'family': family, 'config': dict(vars(cfg))}


def served_family(name):
    """The block module of a served family, or None for another name."""
    if name not in SERVED_FAMILIES:
        return None
    import importlib
    return importlib.import_module('.' + name, __name__)
