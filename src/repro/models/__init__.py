from repro.models.api import (init_cache, init_model, model_decode,  # noqa: F401
                              model_forward, model_prefill, serving_params)
