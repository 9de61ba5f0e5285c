"""paddle_tpu.nn.functional — functional API surface
(reference: python/paddle/nn/functional/__init__.py)."""
from .activation import (  # noqa: F401
    celu, elu, elu_, gelu, glu, gumbel_softmax, hardshrink, hardsigmoid, hardswish,
    hardtanh, leaky_relu, log_sigmoid, log_softmax, maxout, mish, prelu, relu,
    relu6, relu_, rrelu, selu, sigmoid, silu, softmax, softplus, softshrink,
    softmax_, softsign, swish, tanh, tanh_, tanhshrink, thresholded_relu)
from .common import (  # noqa: F401
    alpha_dropout, bilinear, class_center_sample, cosine_similarity, dropout,
    dropout2d, dropout3d, embedding, fold, interpolate, label_smooth, linear,
    one_hot, pad, sequence_mask, unfold, upsample)
from .conv import (  # noqa: F401
    conv1d, conv1d_transpose, conv2d, conv2d_transpose, conv3d, conv3d_transpose)
from .extension import diag_embed, gather_tree, temporal_shift  # noqa: F401
from .loss import (  # noqa: F401
    binary_cross_entropy, binary_cross_entropy_with_logits,
    cosine_embedding_loss, cross_entropy, ctc_loss, dice_loss,
    fused_linear_cross_entropy, hinge_embedding_loss, hsigmoid_loss,
    kl_div, l1_loss, log_loss, margin_ranking_loss, mse_loss, nll_loss,
    npair_loss, sigmoid_focal_loss, smooth_l1_loss, softmax_with_cross_entropy,
    square_error_cost, triplet_margin_loss)
from .norm import (  # noqa: F401
    batch_norm, group_norm, instance_norm, layer_norm, local_response_norm,
    gated_rms_norm, normalize, rms_norm)
from .pooling import (  # noqa: F401
    adaptive_avg_pool1d, adaptive_avg_pool2d, adaptive_avg_pool3d,
    adaptive_max_pool1d, adaptive_max_pool2d, adaptive_max_pool3d, avg_pool1d,
    avg_pool2d, avg_pool3d, max_pool1d, max_pool2d, max_pool3d)
from .vision import (  # noqa: F401
    affine_grid, channel_shuffle, grid_sample, pixel_shuffle, pixel_unshuffle)
from .attention import (  # noqa: F401
    block_diffusion_mask, scaled_dot_product_attention)
from .rotary import rope_frequencies, rotary_embedding  # noqa: F401
from .linear_attention import causal_conv1d, gated_delta_rule  # noqa: F401
from .state_space import ssd_scan  # noqa: F401
