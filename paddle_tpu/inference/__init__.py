from .serving import export_inference, load_exported, InferenceServer
from .batching import (BatchingInferenceServer, bucket_sizes,
                       export_bucketed)
from .blocks import OlmoeBlock, OptBlock, OuroBlock
from .decode import (DecodeEngine, DecodeServer, DecodeStream,
                     decode_buckets, extract_params)
from .fleet import ServingFleet
from .aot_cache import AotCache
from .tenancy import AdmissionError, TenantRegistry, SLO_CLASSES

__all__ = ['export_inference', 'load_exported', 'InferenceServer',
           'BatchingInferenceServer', 'export_bucketed', 'bucket_sizes',
           'DecodeEngine', 'DecodeServer', 'DecodeStream',
           'decode_buckets', 'extract_params', 'OptBlock', 'OlmoeBlock',
           'OuroBlock',
           'ServingFleet', 'AotCache', 'AdmissionError',
           'TenantRegistry', 'SLO_CLASSES']
