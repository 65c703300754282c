from ..telemetry import setup_spans as _setup_spans

# the program's import goes on here (the Pallas kernels behind the models
# are most of it): ``engine.setup_totals["import_s"]`` counts it
with _setup_spans.importing():
    from .transformer import MoEConfig, TransformerConfig, TransformerLM  # noqa: F401
    from .gpt2 import gpt2_config, gpt2_model  # noqa: F401
    from .llama import llama_config, llama_model  # noqa: F401
    from .mixtral import mixtral_config, mixtral_model  # noqa: F401
    from .olmoe import olmoe_config, olmoe_model  # noqa: F401
    from .instella_moe import instella_moe_config, instella_moe_model  # noqa: F401
    from .afmoe import afmoe_config, afmoe_model  # noqa: F401
    from .sdar_moe import sdar_moe_config, sdar_moe_model  # noqa: F401
    from .evabyte import evabyte_config, evabyte_model  # noqa: F401
    from .keye_vl2 import keye_vl2_config, keye_vl2_model  # noqa: F401
    from .xing4 import xing4_config, xing4_model  # noqa: F401
    from .phi4flash import phi4flash_config, phi4flash_model  # noqa: F401
    from .smallthinker import smallthinker_config, smallthinker_model  # noqa: F401
    from .granite_hybrid import granite_hybrid_config, granite_hybrid_model  # noqa: F401
    from .kimi_linear import kimi_linear_config, kimi_linear_model  # noqa: F401
    from .opt_phi_falcon import (falcon_config, falcon_model, opt_config,  # noqa: F401
                                 opt_model, phi_config, phi_model)
    from .bloom_neox_gptj import (bloom_config, bloom_model, gpt_neo_config,  # noqa: F401
                                  gpt_neo_model, gpt_neox_config, gpt_neox_model,
                                  gptj_config, gptj_model)
    from .bert import (bert_config, bert_model, roberta_config,  # noqa: F401
                       roberta_model)
