from .neurons import LIFConfig, lif_step, lif_rollout, spike  # noqa: F401
from .models import (spike_resnet18, spike_resnet50, spike_vgg16,  # noqa: F401
                     spikformer,
                     model_specs, model_rollout, model_step, init_state, SNNConfig)
from .profile import profile_model  # noqa: F401
