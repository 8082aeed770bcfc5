"""Operations and bytes of the kernels and of a whole model step, worked
out from shapes. One module per kernel; ``model`` counts a whole step."""
