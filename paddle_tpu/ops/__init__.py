"""Operator lowerings: each module registers op type -> jax lowering.

The registry (core/registry.py) replaces the reference's 356 REGISTER_OPERATOR
registrations (see SURVEY Appendix A; paddle/fluid/operators/). Every op here
is a pure jax emission into the whole-program trace — XLA provides the kernel,
fusion, and scheduling that the reference implemented per-op in C++/CUDA.
"""
from . import meta
from . import math_ops
from . import activations
from . import tensor_ops
from . import nn_ops
from . import optimizer_ops
from . import compare_ops
from . import random_ops
from . import metrics_ops
from . import sequence_ops
from . import rnn_ops
from . import control_flow_ops
from . import crf_ctc_ops
from . import detection_ops
from . import vision_ops
from . import quant_ops
from . import misc_ops
from . import attention_ops
from . import ce_ops
from . import ffn_ops
from . import embedding_ops
from . import kernel_tier
from . import kv_cache_ops
from . import moe_ops
from . import mla_ops
from . import short_conv_ops
from . import ssm_ops
from . import ssd_ops
from . import gdn_ops
from . import fused_ops
from . import dist_ops
from . import pipeline_ops
from . import health_ops
