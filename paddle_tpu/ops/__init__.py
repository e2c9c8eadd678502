"""Op library: importing this package registers every op's shape inference,
JAX emitter, and grad maker with paddle_tpu.registry (the analog of the
reference's static REGISTER_OPERATOR initializers in paddle/fluid/operators/)."""
from . import math_ops      # noqa: F401
from . import tensor_ops    # noqa: F401
from . import nn_ops        # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import io_ops        # noqa: F401
from . import control_flow_ops  # noqa: F401
from . import array_ops    # noqa: F401
from . import sequence_ops  # noqa: F401
from . import moe_ops       # noqa: F401
from . import dist_ops      # noqa: F401
from . import beam_search_ops  # noqa: F401
from . import fused_ops     # noqa: F401
from . import detection_ops  # noqa: F401
from . import attention_ops  # noqa: F401
from . import loss_ops      # noqa: F401
from . import eval_ops      # noqa: F401
from . import misc_ops      # noqa: F401
from . import nn3d_ops      # noqa: F401
from . import ctc_rnn_ops   # noqa: F401
from . import quant_ops     # noqa: F401
from . import delta_rule_ops  # noqa: F401
from . import ssd_ops      # noqa: F401
from . import latent_attention_ops  # noqa: F401
from . import block_diffusion_ops  # noqa: F401
