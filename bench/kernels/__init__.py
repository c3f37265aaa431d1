"""Operations and bytes that each kernel's work needs, one module per kernel."""
