from .transformer import TransformerConfig, TransformerLM, reference_attention
from .llama import llama2, llama2_config
from .gpt import gpt2, gpt2_config
from .mistral import mistral, mistral_config
from .phi import phi, phi_config
from .qwen import qwen2, qwen2_config
from .opt import opt, opt_config
from .bloom import bloom, bloom_config
from .gptj import gptj, gptj_config
from .gpt_neox import gpt_neox, gpt_neox_config
from .falcon import falcon, falcon_config
from .mellum import mellum, mellum_config
from .trinity import trinity, trinity_config
from .sdar import sdar, sdar_config
from .glm import glm, glm_config
from .solar import solar, solar_config
from .minicpm import minicpm, minicpm_config
from .nemotron import nemotron, nemotron_config
