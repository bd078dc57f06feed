from .logging import logger, log_dist
from .placement import owned_device_put
