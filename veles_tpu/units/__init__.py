from .base import (Avatar, Context, Forward, InputJoiner, LambdaUnit, Spec,
                   TrivialUnit, Unit, UnitRegistry)
from .nn import (Add, All2All, All2AllRELU, All2AllSincos, All2AllSoftmax,
                 All2AllTanh, AvgPooling, Conv, ConvRELU, ConvTanh, Deconv,
                 Depool, Dropout, Evaluator, EvaluatorMSE, EvaluatorSoftmax,
                 Embedding, Flatten, LayerNorm, LRN, MaxPooling,
                 MeanDispNormalizer,
                 Reshape, SeqLast,
                 StochasticAbsPooling)
from .parallel_nn import (MoEFFN, MultiHeadAttention, PipelineStack,
                          expert_rules, pipeline_rules)
from .ssm import Mamba2Mixer
from .linear_attention import GatedDeltaNet, KimiDeltaAttention
from .kohonen import KohonenForward
from .recurrent import GRU, LSTM, RNN
from .rbm import RBM
from .workflow import Workflow, WorkflowError
