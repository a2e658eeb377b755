from .autotuner import Autotuner, TuningResult
from .scheduler import Experiment, ResourceManager
from .tuner import BaseTuner, GridSearchTuner, ModelBasedTuner, RandomTuner
