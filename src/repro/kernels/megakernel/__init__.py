from .bodies import mega_body, mega_capable, mega_lane
from .kernel import CARRY_VMEM_BYTES, mega_stage_kernel
